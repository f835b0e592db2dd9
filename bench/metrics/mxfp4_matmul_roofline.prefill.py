"""Roofline share of the ``mxfp4_matmul`` kernel events inside the prefill
program (single-shot ``prefill`` or chunked ``chunk``), device trace."""
import harness


def reduce(rec):
    eng = rec["cell"]["engine"]
    chunk = eng.get("chunk_len")
    calls = harness.work("decoder_lm").matmul_calls(
        rec["config"]["model"], chunk or eng["prefill_len"], 1)
    return harness.work("mxfp4_matmul").roofline_share(
        rec, "chunk" if chunk else "prefill", calls)
