"""Bursty open-loop arrivals at the mix's mean ``rate_per_s``: Poisson
at ``rate_per_s * (on_s + off_s) / on_s`` for ``on_s`` seconds, then
nothing for ``off_s`` seconds, from the window's start."""
import numpy as np

from bench.lib import keys as K


def due(rng, spec, seconds):
    on, off = float(spec["on_s"]), float(spec["off_s"])
    peak = float(spec["rate_per_s"]) * (on + off) / on
    # a Poisson stream over the on-time alone, laid out on the clock
    t = K.poisson_arrivals(rng, peak, seconds * on / (on + off) + on)
    t = np.floor(t / on) * (on + off) + np.mod(t, on)
    return t[t < seconds]
