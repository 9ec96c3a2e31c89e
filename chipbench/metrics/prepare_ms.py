"""Mean host span (ms) of the engine's prepare stage,
``SketchEngine._prepare_ready`` (hash matmul, keep decisions, sort), over
the prepares that ended in the window; it blocks until ready."""
import numpy as np


def read(ctx):
    spans = ctx.spans("prepare")
    if not spans:
        return None
    return 1e3 * float(np.mean([t1 - t0 for _, t0, t1, _ in spans]))
