"""Model FLOPs of the tokens the active lanes decoded (each at its own
context length), over the summed walls of the engine's decode steps, as a
share of the chip's bf16 peak."""
import harness


def reduce(rec):
    if "peaks" not in rec:
        return None
    lm = harness.work("decoder_lm")
    m = rec["config"]["model"]
    walls = sum(t1 - t0 for k, _, _, t0, t1 in rec.get("steps", ())
                if k == "decode")
    flops = 0.0
    for r in rec.get("requests", ()):
        p = r["n_prompt"]
        # token 0 comes from the prefill; token j from the decode step that
        # feeds token j-1 at position p + j - 1
        flops += sum(lm.token_flops(m, p + j) for j in range(1, len(r["times"])))
    if walls <= 0 or flops <= 0:
        return None
    return 100.0 * flops / walls / rec["peaks"]["bf16_flops"]
