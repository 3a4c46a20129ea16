"""shed_pct (%): requests the scheduler dropped or rejected because they
could no longer meet their SLO, over all released in the window.  Each is a
miss in ``finish_rate``."""

import stats


def read(run):
    return 100.0 * stats.shed(run.window.requests) / len(run.window.requests)
