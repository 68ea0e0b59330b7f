"""Median host time from a decode step's start to the return of the
jitted call, before the sync (host clock, ms)."""

import numpy as np


def read(facts):
    d = facts.get("host_dispatch_s")
    return float(np.median(d)) * 1e3 if d else None
