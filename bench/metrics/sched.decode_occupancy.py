"""Scheduler: share of the decode pool's rows that produced a token.

Decode tokens emitted (every served token after each request's first)
over decode steps x slots, over the whole window. Counts from the
server's own counters (``decode_steps``) and the served token lists."""


def read(run):
    steps = run.counts.get("decode_steps", 0)
    if not steps:
        return None
    return 100.0 * run.counts["decode_tokens"] / (
        steps * run.counts["max_batch"])
