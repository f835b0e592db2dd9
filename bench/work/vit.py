"""Operations one ViT frame needs, from the configuration's published
sizes: each multiply-add of the linears (patch embedding, QKV/O, MLP,
head on the class token) as two operations, and attention's two products
over all tokens; norms, softmax and GELU left out."""


def dims(m: dict) -> dict:
    g = m["image_size"] // m["patch_size"]
    return {"d": m["hidden_size"], "f": m["intermediate_size"],
            "n": m["num_hidden_layers"], "c": m["num_classes"],
            "patches": g * g, "seq": g * g + 1,
            "pdim": m["patch_size"] ** 2 * m["num_channels"]}


def matmul_calls(m: dict) -> list:
    """(M, K, N) of every linear of one frame, in execution order."""
    s = dims(m)
    d, f, t = s["d"], s["f"], s["seq"]
    layer = [(t, d, d)] * 4 + [(t, d, f), (t, f, d)]
    return ([(s["patches"], s["pdim"], d)] + layer * s["n"]
            + [(1, d, s["c"])])


def frame_flops(m: dict) -> float:
    s = dims(m)
    lin = sum(2.0 * mm * k * n for mm, k, n in matmul_calls(m))
    return lin + s["n"] * 4.0 * s["seq"] ** 2 * s["d"]
