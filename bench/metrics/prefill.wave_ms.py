"""Model step, prefill: device time of one admission wave's prefill
program, from the trace (program executions matched by name)."""

PROGRAM = r"^jit_prefill_fn\("


def read(run):
    if run.trace is None:
        return None
    sec, n = run.trace.program_time(PROGRAM)
    return 1e3 * sec / n if n else None
