"""Whole decode step's share of the chip's bf16 peak.

Model operations of the traced decode steps (``kernels/decode_step.<family>``:
2 x matmul parameters per live token plus the mixing work on the live
context) over (decode programs' device time x peak). The steps' live
contexts come from the host's log of each decode dispatch inside the
traced stretch, which opens and closes on a device sync, so the log and
the trace hold the same steps (a count that differs reads nothing)."""

PROGRAM = r"^jit_decode_fn\("


def read(run):
    if run.trace is None or not run.steps:
        return None
    sec, n = run.trace.program_time(PROGRAM)
    if not n or n != len(run.steps):
        run.note("decode.mfu", f"{n} decode programs traced against "
                 f"{len(run.steps)} steps logged: nothing read")
        return None
    k = run.kernel(f"decode_step.{run.family}")
    work = sum(k.flops(run.model, ctx) for ctx in run.steps)
    return 100.0 * work / (sec * run.peaks["bf16_flops_s"])
