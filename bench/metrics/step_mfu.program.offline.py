"""Whole steps' share of the chip's peaks from the program's own step spans
(offline cells), in per cent: each step's useful work at the peaks, the
ternary matmuls of its real tokens (``m_real``) at the int8 peak, attention
over the keys they need (``attn_keys``) and the head rows that served a
token (``logit_rows``) at the bf16 peak, over the steps' summed ``dur``.
Padding counts as loss. The operations come from the configuration's kind
module. None where the spans carry no such counts."""


def read(run):
    spans = [s for s in run.spans
             if s["name"].startswith("engine_step/") and "attn_keys" in s["args"]]
    if not spans:
        return None
    cfg, kind, pk = run.cell.config, run.cell.kind, run.peaks
    mm = kind.mpgemm_ops_per_token(cfg)
    at_peak = sum(
        s["args"]["m_real"] * mm / pk["int8_ops"]
        + (kind.attention_ops(cfg, s["args"]["attn_keys"])
           + s["args"]["logit_rows"] * kind.head_ops(cfg)) / pk["bf16_flops"]
        for s in spans)
    return 100.0 * at_peak / (sum(s["dur"] for s in spans) / 1e6)
