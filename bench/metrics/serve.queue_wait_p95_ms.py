"""95th percentile of the serving tier's queue wait (the program's
``serving.queue_wait_s`` observations, submit to flush start) over the
requests flushed in the window."""

import numpy as np


def read(r):
    xs = r.observations.get("serving.queue_wait_s")
    if not xs:
        return None
    return 1e3 * float(np.percentile(xs, 95))
