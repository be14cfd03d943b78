"""`scatter_append`'s share of its roofline, in percent.

The append of k rows of width w needs k * w * 4 bytes read from the
delta and the same written into the extent (`append_bytes`), whatever
implements it; it does no arithmetic.  The least time is those bytes
over the chip's HBM bandwidth; the time taken is the device time of the
kernel's jitted program (`jit_scatter_append_pallas`) in the trace."""

PROGRAM = "jit_scatter_append_pallas"


def append_bytes(k: int, w: int) -> int:
    return 2 * k * w * 4


def read(ctx):
    tr = ctx.trace
    if tr is None or not ctx.appends:
        return None
    t = tr.module_seconds(PROGRAM)
    if t <= 0:
        return None
    need = sum(append_bytes(k, w) for k, w in ctx.appends)
    return need / ctx.peak("hbm_bytes_per_s") / t * 100.0
