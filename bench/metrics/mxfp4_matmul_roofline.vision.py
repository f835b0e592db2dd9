"""Roofline share of the ``mxfp4_matmul`` kernel events of the ViT frame
program (patch embedding, 72 block linears, head), device trace."""
import harness


def reduce(rec):
    calls = harness.work("vit").matmul_calls(rec["config"]["model"])
    return harness.work("mxfp4_matmul").roofline_share(rec, "", calls)
