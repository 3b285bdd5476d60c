"""Load generator: how late it sent, the 95th percentile of send time
minus due time over the window's requests (ms, client clock). A starved
generator must not read as a fast server."""

import numpy as np


def read(ctx):
    log = ctx["log"]
    late = (log["sent"] - log["due"])[log["timed"] & (log["sent"] > 0)]
    if not len(late):
        return None
    return float(np.quantile(late, 0.95) * 1e3)
