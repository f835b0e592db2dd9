"""Operations and bytes one ``mxfp4_matmul`` call needs: x [M, K] bf16
times a weight held as packed E2M1 codes (two per byte) and one E8M0
exponent byte per 32 along K, into a bf16 [M, N] output."""


def rows(m: int) -> int:
    """Rows the kernel executes: M padded up to its 16-row alignment
    (``kernels/mxfp4_matmul/ops.py::pick_bm``: min(128, M rounded to 16),
    then M rounded to that tile)."""
    bm = min(128, -(-max(m, 1) // 16) * 16)
    return -(-m // bm) * bm


def work(m: int, k: int, n: int) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of one call at M executed rows."""
    flops = 2.0 * m * k * n
    nbytes = k * n / 2 + k / 32 * n + 2.0 * m * k + 2.0 * m * n
    return flops, nbytes


def min_seconds(calls, peak_flops: float, hbm_bw: float) -> float:
    """Least time the chip could take for ``calls`` [(M, K, N)]: each call
    bound by the larger of compute and memory time."""
    t = 0.0
    for m, k, n in calls:
        f, b = work(m, k, n)
        t += max(f / peak_flops, b / hbm_bw)
    return t


def roofline_share(rec, module: str, calls: list):
    """Percent of the roofline reached by the ``mxfp4_matmul`` events in
    programs whose name holds ``module``: the least time one program's
    ``calls`` [(M, K, N)] need, per call, over the device time each event
    took. None where the trace holds no such event."""
    import devtrace

    tr = rec.get("trace")
    if tr is None or "peaks" not in rec:
        return None
    ns, n = devtrace.kernel_ns(tr, "mxfp4_matmul", module)
    if n == 0 or ns <= 0:
        return None
    pk = rec["peaks"]
    calls = [(rows(m), k, nn) for m, k, nn in calls]
    per_call = min_seconds(calls, pk["bf16_flops"], pk["hbm_bytes_per_s"])
    return 100.0 * per_call / len(calls) * n / (ns / 1e9)
