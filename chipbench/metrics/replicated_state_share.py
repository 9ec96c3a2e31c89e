"""Share (%) of one chip's index state that every chip holds alike:
100 · replicated / per_chip of ``RetrievalService.stats()
["shard_state_bytes"]``, read from the service by the cell's check after
the window (``run.svc_stats``).  None where the program has no such
counter."""


def read(ctx):
    stats = getattr(ctx.run, "svc_stats", None) or {}
    b = stats.get("shard_state_bytes")
    if not b or not b.get("per_chip"):
        return None
    return 100.0 * b["replicated"] / b["per_chip"]
