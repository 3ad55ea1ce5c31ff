"""Lognormal lengths: ``median`` * exp(``sigma`` * z), z standard
normal."""
from statistics import NormalDist

import numpy as np


def quantile(spec, q):
    z = np.asarray([NormalDist().inv_cdf(float(p)) for p in q])
    return spec["median"] * np.exp(spec["sigma"] * z)
