"""The time the published HBM bandwidth allows the full layers' indexer in
one decode iteration (`peaks_dsa.index_decode_bytes`: its projections and
every visible index key of every row, at the rows' mean contexts) as a
share of the device time under `layer/latent_attn/indexer/score` and
`.../topk` of `gen/decode_step`, in %.  The score is bound by the keys'
bytes (128 values a slot against 64 x 128 multiply-adds: 64 FLOPs a byte,
under the chip's 240), so bytes are the roofline."""
from benchmark import peaks_dsa
from benchmark.metrics import _dsa


def read(run):
    if run.peaks is None:
        return None
    score = _dsa.decode_ms(run, "layer/latent_attn/indexer/score")
    topk = _dsa.decode_ms(run, "layer/latent_attn/indexer/topk")
    proj = _dsa.decode_ms(run, "layer/latent_attn/indexer/proj")
    if score is None or topk is None or proj is None:
        return None
    cfg = run.model_cfg
    n_full = cfg.window_pattern.count("F")
    floor_s = n_full * peaks_dsa.index_decode_bytes(cfg, _dsa.contexts(run)) / (
        run.chips * run.peaks["hbm_bytes_per_s"])
    return 100.0 * floor_s * 1e3 / (score + topk + proj)
