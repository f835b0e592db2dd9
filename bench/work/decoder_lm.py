"""Operations a decoder LM's forward pass needs, from its configuration's
published sizes (the ``model`` block of ``bench/configs/<config>.json``).

Model FLOPs count each multiply-add of the linears as two operations and
attention's two products over the keys a token attends to; norms,
softmax and rotary embedding are left out, as is usual for MFU."""


def dims(m: dict) -> dict:
    d, h = m["hidden_size"], m["num_attention_heads"]
    hd = m.get("head_dim") or d // h
    return {"d": d, "q": h * hd, "kv": m["num_key_value_heads"] * hd,
            "f": m["intermediate_size"], "v": m["vocab_size"],
            "n": m["num_hidden_layers"],
            "w": m.get("sliding_window") or 0}


def layer_linears(m: dict) -> list[tuple[int, int]]:
    """(K, N) of each linear of one layer, in execution order."""
    s = dims(m)
    d, q, kv, f = s["d"], s["q"], s["kv"], s["f"]
    return [(d, q), (d, kv), (d, kv), (q, d), (d, f), (d, f), (f, d)]


def linear_params(m: dict) -> int:
    s = dims(m)
    return s["n"] * sum(k * n for k, n in layer_linears(m)) + s["d"] * s["v"]


def token_flops(m: dict, context: int) -> float:
    """FLOPs for one token that attends to ``context`` keys (itself
    included), output head included."""
    s = dims(m)
    ctx = min(context, s["w"]) if s["w"] else context
    return 2.0 * linear_params(m) + 4.0 * s["n"] * s["q"] * ctx


def prompt_flops(m: dict, n_prompt: int) -> float:
    """A whole prompt: token t attends to t + 1 keys. The head runs on the
    last position only, as the serving prefill does."""
    s = dims(m)
    body = 2.0 * (linear_params(m) - s["d"] * s["v"])
    ctx = sum(min(t + 1, s["w"]) if s["w"] else t + 1
              for t in range(n_prompt))
    return n_prompt * body + 4.0 * s["n"] * s["q"] * ctx + 2.0 * s["d"] * s["v"]


def matmul_calls(m: dict, rows: int, head_rows: int) -> list:
    """(M, K, N) of every MXFP4 linear in one forward over ``rows``
    token rows, with the output head over ``head_rows``."""
    s = dims(m)
    calls = [(rows, k, n) for _ in range(s["n"]) for k, n in layer_linears(m)]
    return calls + [(head_rows, s["d"], s["v"])]
