"""Gateway layer: 95th percentile of the client-side time of ``POST
/campaigns``, from the load client's own timestamps (ms)."""

import numpy as np


def read(ctx):
    s = ctx["run"].extra.get("submit_s")
    return 1000.0 * float(np.percentile(s, 95)) if s else None
