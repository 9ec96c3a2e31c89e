"""Mean host span (ms) of the engine's commit stage,
``SketchEngine._commit_one``, per chunk, over the commits that ended in
the window; it blocks until the new state is ready."""
import numpy as np


def read(ctx):
    spans = ctx.spans("commit")
    if not spans:
        return None
    return 1e3 * float(np.mean([t1 - t0 for _, t0, t1, _ in spans]))
