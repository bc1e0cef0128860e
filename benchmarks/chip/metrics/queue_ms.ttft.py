"""Scheduler queue (serving/server.py ContinuousServePlanner): mean over
the requests due in the window of due time to the Intent that proposed
the admission that took it."""
from chipbench import measures


def read(run):
    return measures.mean([(run.steps[q["admit_step"]]["intent_ts"] - q["due"])
                          * 1e3 for q in run.requests
                          if q["admit_step"] is not None])
