"""Fixture tests for arealint: per rule family a true positive it
catches, a negative it allows, and a suppressed variant; plus regression
pins on the suppression-comment and JSON output formats."""

import json
import subprocess
import sys
import textwrap

import pytest

from areal_tpu.analysis import (
    Severity,
    get_rules,
    lint_source,
    render_human,
    render_json,
)
from areal_tpu.analysis.rules import RULE_NAMES


def lint(src, rules=None):
    return lint_source(textwrap.dedent(src), path="snippet.py", rules=rules)


def errors(findings, rule=None):
    return [
        f for f in findings
        if f.severity == Severity.ERROR and (rule is None or f.rule == rule)
    ]


def warnings(findings, rule=None):
    return [
        f for f in findings
        if f.severity == Severity.WARNING
        and (rule is None or f.rule == rule)
    ]


# ---------------------------------------------------------------- host-sync


class TestHostSync:
    def test_per_scalar_float_on_device_value_in_hot_loop(self):
        fs = lint("""
            def decode_chunk_loop(self, xs):
                out = decode_fn(xs)
                acc = []
                for t in range(8):
                    acc.append(float(out[t]))
                return acc
        """)
        errs = errors(fs, "host-sync")
        assert len(errs) == 1 and errs[0].line == 6

    def test_batched_to_host_is_clean(self):
        fs = lint("""
            def decode_chunk_loop(self, xs):
                out = decode_fn(xs)
                out = to_host(out)
                acc = []
                for t in range(8):
                    acc.append(float(out[t]))
                return acc
        """)
        assert not errors(fs, "host-sync")
        assert not warnings(fs, "host-sync")

    def test_tolist_batch_is_clean(self):
        fs = lint("""
            def decode_chunk_loop(self, xs):
                out = decode_fn(xs)
                vals = out.tolist()
                for t in range(8):
                    keep(float(vals[t]))
        """)
        assert not errors(fs, "host-sync")

    def test_unknown_operand_in_hot_loop_warns_only(self):
        fs = lint("""
            def _drain_chunk_outputs(self, out_logps):
                for t in range(8):
                    keep(float(out_logps[t]))
        """)
        assert not errors(fs, "host-sync")
        assert len(warnings(fs, "host-sync")) == 1

    def test_item_on_device_value_errors(self):
        fs = lint("""
            def gen_chunk(self):
                y = jnp.sum(x)
                while cond():
                    use(y.item())
        """)
        assert len(errors(fs, "host-sync")) == 1

    def test_implicit_bool_branch_on_device_value(self):
        fs = lint("""
            def decode_step(self, xs):
                done = decode_fn(xs)
                if done:
                    return None
        """)
        assert len(errors(fs, "host-sync")) == 1

    def test_block_until_ready_needs_span(self):
        fs = lint("""
            def generate(self, xs):
                out = fwd_fn(xs)
                out.block_until_ready()
        """)
        assert len(errors(fs, "host-sync")) == 1

    def test_block_until_ready_inside_span_is_clean(self):
        fs = lint("""
            def generate(self, xs):
                out = fwd_fn(xs)
                with tracer.span("decode_chunk", cat="compute"):
                    out.block_until_ready()
        """)
        assert not errors(fs, "host-sync")

    def test_cold_function_not_checked(self):
        fs = lint("""
            def summarize(self, xs):
                out = decode_fn(xs)
                for t in range(8):
                    keep(float(out[t]))
        """)
        assert not errors(fs, "host-sync")

    def test_suppressed_with_reason(self):
        fs = lint("""
            def decode_chunk_loop(self, xs):
                out = decode_fn(xs)
                for t in range(8):
                    keep(float(out[t]))  # arealint: ignore[host-sync] -- drain boundary: one live slot
        """)
        assert not errors(fs, "host-sync")


# ----------------------------------------------------------- retrace-hazard


class TestRetraceHazard:
    def test_jit_inside_loop_errors(self):
        fs = lint("""
            def run(xs):
                for x in xs:
                    f = jax.jit(step)
                    f(x)
        """)
        assert len(errors(fs, "retrace-hazard")) == 1

    def test_inline_jit_call_inside_loop_errors(self):
        fs = lint("""
            def run(xs):
                for x in xs:
                    y = jax.jit(step)(x)
        """)
        assert errors(fs, "retrace-hazard")

    def test_hoisted_jit_is_clean(self):
        fs = lint("""
            def run(xs):
                f = jax.jit(step)
                for x in xs:
                    f(x)
        """)
        assert not errors(fs, "retrace-hazard")

    def test_asarray_of_listcomp_in_loop_errors(self):
        fs = lint("""
            def refill(admits):
                while admits:
                    fn(jnp.asarray([len(t) for t in admits]))
        """)
        assert len(errors(fs, "retrace-hazard")) == 1

    def test_asarray_of_grown_list_warns(self):
        fs = lint("""
            def refill(admits):
                rows = []
                for a in admits:
                    rows.append(a)
                    fn(jnp.asarray(rows))
        """)
        assert not errors(fs, "retrace-hazard")
        assert len(warnings(fs, "retrace-hazard")) == 1

    def test_asarray_of_padded_buffer_is_clean(self):
        # the _pack_admits idiom: numpy-padded fixed-shape buffer
        fs = lint("""
            def refill(self, admits, n_slots):
                while admits:
                    rows, plens, slots = self._pack_admits(admits, n_slots)
                    fn(jnp.asarray(rows), jnp.asarray(plens))
        """)
        assert not errors(fs, "retrace-hazard")
        assert not warnings(fs, "retrace-hazard")

    def test_shape_scalar_into_nonstatic_jit_warns(self):
        fs = lint("""
            def run(xs):
                f = jax.jit(step)
                f(xs, len(xs))
        """)
        assert len(warnings(fs, "retrace-hazard")) == 1

    def test_shape_scalar_with_static_argnums_is_clean(self):
        fs = lint("""
            def run(xs):
                f = jax.jit(step, static_argnums=(1,))
                f(xs, len(xs))
        """)
        assert not warnings(fs, "retrace-hazard")

    def test_suppressed(self):
        fs = lint("""
            def run(xs):
                for x in xs:
                    f = jax.jit(step)  # arealint: ignore[retrace-hazard] -- profiling sweep
                    f(x)
        """)
        assert not errors(fs, "retrace-hazard")


# ----------------------------------------------------------- async-blocking


class TestAsyncBlocking:
    def test_time_sleep_in_coroutine_errors(self):
        fs = lint("""
            import time
            async def pump(self):
                time.sleep(0.1)
        """)
        assert len(errors(fs, "async-blocking")) == 1

    def test_asyncio_sleep_is_clean(self):
        fs = lint("""
            import asyncio
            async def pump(self):
                await asyncio.sleep(0.1)
        """)
        assert not errors(fs, "async-blocking")

    def test_sleep_in_plain_thread_function_is_clean(self):
        fs = lint("""
            import time
            def collect_loop(self):
                time.sleep(0.1)
        """)
        assert not errors(fs, "async-blocking")

    def test_requests_in_coroutine_errors(self):
        fs = lint("""
            async def fetch(self, url):
                return requests.get(url)
        """)
        assert len(errors(fs, "async-blocking")) == 1

    def test_sync_zmq_recv_errors_awaited_is_clean(self):
        bad = lint("""
            async def pull(self):
                return self.sock.recv_json()
        """)
        good = lint("""
            async def pull(self):
                return await self.sock.recv_json()
        """)
        assert len(errors(bad, "async-blocking")) == 1
        assert not errors(good, "async-blocking")

    def test_open_in_coroutine_warns(self):
        fs = lint("""
            async def load(self, p):
                with open(p) as f:
                    return f.read()
        """)
        assert not errors(fs, "async-blocking")
        assert len(warnings(fs, "async-blocking")) == 1

    def test_await_while_holding_sync_lock_errors(self):
        fs = lint("""
            async def push(self):
                with self._lock:
                    await self.send()
        """)
        assert len(errors(fs, "async-blocking")) == 1

    def test_await_outside_lock_is_clean(self):
        fs = lint("""
            async def push(self):
                with self._lock:
                    stage(self.buf)
                await self.send()
        """)
        assert not errors(fs, "async-blocking")

    def test_suppressed(self):
        fs = lint("""
            import time
            async def pump(self):
                time.sleep(0.1)  # arealint: ignore[async-blocking] -- startup-only path, loop not running yet
        """)
        assert not errors(fs, "async-blocking")


# ----------------------------------------------------------------- sharding


class TestSharding:
    def test_unknown_partitionspec_axis_errors(self):
        fs = lint("""
            AXIS_ORDER = ("pipe", "data", "model")
            from jax.sharding import PartitionSpec as P
            spec = P("data", "modle")
        """)
        errs = errors(fs, "sharding")
        assert len(errs) == 1 and "'modle'" in errs[0].message

    def test_declared_axes_are_clean(self):
        fs = lint("""
            AXIS_ORDER = ("pipe", "data", "model")
            from jax.sharding import PartitionSpec as P
            spec = P(None, ("data", "model"))
        """)
        assert not errors(fs, "sharding")

    def test_no_declared_mesh_skips_axis_check(self):
        fs = lint("""
            from jax.sharding import PartitionSpec as P
            spec = P("anything")
        """)
        assert not errors(fs, "sharding")

    def test_axis_names_kwarg_declares_axes(self):
        fs = lint("""
            from jax.sharding import PartitionSpec as P
            mesh = make_mesh(devs, axis_names=("dp", "tp"))
            spec = P("dp")
            bad = P("pp")
        """)
        errs = errors(fs, "sharding")
        assert len(errs) == 1 and "'pp'" in errs[0].message


# --------------------------------------------------------------- stats-keys


class TestStatsKeys:
    def test_duplicate_key_errors(self):
        fs = lint("""
            stats = {"loss": 1.0, "kl": 2.0, "loss": 3.0}
        """)
        errs = errors(fs, "stats-keys")
        assert len(errs) == 1 and "'loss'" in errs[0].message

    def test_denominator_without_mean_errors(self):
        fs = lint("""
            stats = {"reward_denominator": 8.0}
        """)
        assert len(errors(fs, "stats-keys")) == 1

    def test_denominator_with_mean_is_clean(self):
        fs = lint("""
            stats = {"reward": 0.5, "reward_denominator": 8.0}
        """)
        assert not errors(fs, "stats-keys")

    def test_distinct_keys_are_clean(self):
        fs = lint("""
            stats = {"loss": 1.0, "kl": 2.0, **extra}
        """)
        assert not errors(fs, "stats-keys")

    def test_suppressed(self):
        fs = lint("""
            stats = {"n_denominator": 8.0}  # arealint: ignore[stats-keys] -- mean joined downstream in merge_stats
        """)
        assert not errors(fs, "stats-keys")


# -------------------------------------------------- suppression machinery


class TestSuppressions:
    def test_missing_reason_is_an_error(self):
        fs = lint("""
            stats = {"n_denominator": 8.0}  # arealint: ignore[stats-keys]
        """)
        errs = errors(fs, "suppression")
        assert len(errs) == 1 and "reason" in errs[0].message
        # and the finding itself is NOT suppressed by a reasonless comment
        assert errors(fs, "stats-keys")

    def test_own_line_comment_covers_next_code_line(self):
        fs = lint("""
            # arealint: ignore[stats-keys] -- covered by the next-line rule
            stats = {"n_denominator": 8.0}
        """)
        assert not errors(fs, "stats-keys")

    def test_own_line_comment_skips_comment_block(self):
        fs = lint("""
            # arealint: ignore[stats-keys] -- reason text here
            # (continuation prose of the justification)
            stats = {"n_denominator": 8.0}
        """)
        assert not errors(fs, "stats-keys")

    def test_star_suppresses_any_rule(self):
        fs = lint("""
            stats = {"n_denominator": 8.0}  # arealint: ignore[*] -- fixture
        """)
        assert not errors(fs)

    def test_wrong_rule_does_not_suppress(self):
        fs = lint("""
            stats = {"n_denominator": 8.0}  # arealint: ignore[host-sync] -- wrong family
        """)
        assert errors(fs, "stats-keys")

    def test_unused_suppression_reported_as_info(self):
        fs = lint("""
            x = 1  # arealint: ignore[host-sync] -- nothing here to suppress
        """)
        assert [f for f in fs if f.rule == "unused-suppression"
                and f.severity == Severity.INFO]

    def test_syntax_error_reported_not_raised(self):
        fs = lint("def broken(:\n")
        assert errors(fs, "parse")


# ------------------------------------------------------------ output formats


class TestOutputFormats:
    SRC = 'stats = {"n_denominator": 8.0}\n'

    def test_json_schema_is_stable(self):
        fs = lint(self.SRC)
        payload = json.loads(render_json(fs))
        assert payload["version"] == 1
        assert set(payload) == {"version", "counts", "findings"}
        assert set(payload["counts"]) == {"error", "warning", "info"}
        assert payload["counts"]["error"] == 1
        (f,) = payload["findings"]
        assert set(f) == {
            "rule", "severity", "path", "line", "col", "message"
        }
        assert f["rule"] == "stats-keys"
        assert f["severity"] == "error"
        assert f["path"] == "snippet.py"
        assert f["line"] == 1
        assert isinstance(f["col"], int)

    def test_human_format(self):
        fs = lint(self.SRC)
        text = render_human(fs)
        assert text.splitlines()[0].startswith("snippet.py:1:")
        assert "error[stats-keys]" in text
        assert text.splitlines()[-1] == (
            "arealint: 1 error(s), 0 warning(s), 0 info(s)"
        )

    def test_findings_sorted_deterministically(self):
        src = (
            'a = {"x_denominator": 1.0}\n'
            'b = {"y": 1, "y": 2}\n'
        )
        fs = lint(src)
        assert [f.line for f in fs] == sorted(f.line for f in fs)

    def test_rule_registry_names(self):
        assert RULE_NAMES == (
            "host-sync", "retrace-hazard", "async-blocking", "sharding",
            "stats-keys", "metrics-names",
        )
        with pytest.raises(KeyError):
            get_rules(["no-such-rule"])


# ------------------------------------------------------------------ the CLI


class TestCli:
    def _run(self, args, cwd):
        return subprocess.run(
            [sys.executable, "-m", "areal_tpu.apps.lint", *args],
            capture_output=True, text=True, cwd=cwd,
        )

    def test_cli_exit_codes_and_json(self, tmp_path):
        import os
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        bad = tmp_path / "bad.py"
        bad.write_text('stats = {"n_denominator": 8.0}\n')
        good = tmp_path / "good.py"
        good.write_text('stats = {"n": 1.0, "n_denominator": 8.0}\n')
        env_cwd = repo  # so `areal_tpu` is importable without install

        r = self._run([str(bad), "--json"], env_cwd)
        assert r.returncode == 1, r.stderr
        payload = json.loads(r.stdout)
        assert payload["counts"]["error"] == 1

        r = self._run([str(good)], env_cwd)
        assert r.returncode == 0, r.stderr + r.stdout

        r = self._run(["--list-rules"], env_cwd)
        assert r.returncode == 0
        assert r.stdout.split() == list(RULE_NAMES)

        r = self._run([str(tmp_path / "missing.py")], env_cwd)
        assert r.returncode == 2
