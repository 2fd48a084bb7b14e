"""Open-loop Poisson arrivals at the mix's ``rate_per_s``: independent
users, each request due at its own time whatever the server does."""
from bench.lib import keys as K


def due(rng, spec, seconds):
    return K.poisson_arrivals(rng, float(spec["rate_per_s"]), seconds)
