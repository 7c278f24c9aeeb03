"""95th percentile of job latency over every job the open window sent:
answer time less the job's due time, so a late sender does not hide
queueing.  Nothing where a job went unanswered (that run reads
``missing`` above its limit)."""
import numpy as np


def read(run):
    latency = [j.latency for j in run.jobs]
    if not latency or None in latency:
        return None
    return float(np.percentile(latency, 95))
