"""Live pages over addressed pages of the serving chunk's attention, in %,
over the timed steps: `last_pool_stats["pages_live"]` (the pages under
the live lanes' windows) over `["pages_addressed"]` (lanes x page-table
width) — the share of what the page tables name that the paged kernel has
to read.  None off the serving plane, and where the program keeps no such
counter."""


def read(run):
    pools = [s["pool"] for s in run.steps]
    addressed = sum(p.get("pages_addressed", 0) for p in pools)
    if not addressed:
        return None
    return 100.0 * sum(p["pages_live"] for p in pools) / addressed
