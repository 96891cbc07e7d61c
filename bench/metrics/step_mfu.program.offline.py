"""Whole steps' share of the chip's peaks from the program's own step spans
(offline cells): the formula of ``readers.step_mfu_pct``, with each step's
work from its ``engine_step`` span's ``m_real``, ``attn_keys`` and
``logit_rows`` and its time from the span's ``dur``. None where the spans
carry no such counts."""
import costs


def read(run):
    spans = [s for s in run.spans
             if s["name"].startswith("engine_step/") and "attn_keys" in s["args"]]
    if not spans:
        return None
    cfg, pk = run.cell.config, run.peaks
    mm = costs.mpgemm_ops_per_token(cfg)
    at_peak = sum(
        s["args"]["m_real"] * mm / pk["int8_ops"]
        + (costs.attention_ops(cfg, s["args"]["attn_keys"])
           + s["args"]["logit_rows"] * costs.head_ops(cfg)) / pk["bf16_flops"]
        for s in spans)
    return 100.0 * at_peak / (sum(s["dur"] for s in spans) / 1e6)
