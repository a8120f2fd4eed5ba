"""Scheduler: median of admission minus scheduled arrival (the engine's
own timestamps) over the requests that arrived and were admitted inside
the traced slice, once the slots have filled."""
import numpy as np


def read(run):
    sl = run.window["traced"]
    if not sl:
        return None
    waits = [r["admitted_s"] - r["arrival_s"] for r in run.window["requests"]
             if r["admitted_s"] is not None
             and r["arrival_s"] >= sl["t0"] and r["admitted_s"] <= sl["t1"]]
    return 1e3 * float(np.median(waits)) if waits else None
