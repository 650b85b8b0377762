"""Kernel: the Pallas flash-decode kernel's share of its roofline.

For each traced call, the least time the chip could take, the larger of
operations / peak bf16 rate and bytes / HBM bandwidth
(``kernels/flash_decode``: live K/V at bf16 and the true head dim), summed
and divided by the kernel's summed device time. One call per layer per
decode step; the live contexts come from the host's log of each decode
dispatch in the traced stretch."""

KERNEL = r"^%decode_attention_kernel[.0-9]* = "


def read(run):
    if run.trace is None or not run.steps:
        return None
    sec, n = run.trace.op_time(KERNEL)
    layers = run.model["n_layers"]
    if not n or n != layers * len(run.steps):
        return None
    k = run.kernel("flash_decode")
    p = run.peaks
    bound = t_flop = t_byte = 0.0
    for ctx in run.steps:
        tf = k.flops(run.model, ctx) / p["bf16_flops_s"]
        tb = k.bytes_moved(run.model, ctx) / p["hbm_bytes_s"]
        bound += layers * max(tf, tb)
        t_flop += layers * tf
        t_byte += layers * tb
    run.note("flash_decode_roofline bound by",
             "HBM bytes" if t_byte >= t_flop else "bf16 operations")
    return 100.0 * bound / sec
