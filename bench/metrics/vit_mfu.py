"""Model FLOPs per frame times the traced run's frames per second, as a
share of the chip's bf16 peak."""
import harness


def reduce(rec):
    if "peaks" not in rec:
        return None
    fps = harness.reducer("frames_per_s")(rec)
    if not fps:
        return None
    flops = harness.work("vit").frame_flops(rec["config"]["model"])
    return 100.0 * flops * fps / rec["peaks"]["bf16_flops"]
