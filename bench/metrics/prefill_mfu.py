"""Model FLOPs of the real prompt tokens of every request prefilled, over
the summed walls of the engine's prefill steps, as a share of the chip's
bf16 peak."""
import harness


def reduce(rec):
    if "peaks" not in rec:
        return None
    lm = harness.work("decoder_lm")
    m = rec["config"]["model"]
    walls = sum(t1 - t0 for k, _, _, t0, t1 in rec.get("steps", ())
                if k == "prefill")
    flops = sum(lm.prompt_flops(m, r["n_prompt"])
                for r in rec.get("requests", ()) if r["times"])
    if walls <= 0 or flops <= 0:
        return None
    return 100.0 * flops / walls / rec["peaks"]["bf16_flops"]
