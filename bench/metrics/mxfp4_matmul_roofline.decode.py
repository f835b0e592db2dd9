"""Roofline share of the ``mxfp4_matmul`` kernel events inside the decode
program (every lane's row, parked lanes included), device trace."""
import harness


def reduce(rec):
    lanes = rec["cell"]["engine"]["lanes"]
    calls = harness.work("decoder_lm").matmul_calls(
        rec["config"]["model"], lanes, lanes)
    return harness.work("mxfp4_matmul").roofline_share(rec, "decode", calls)
