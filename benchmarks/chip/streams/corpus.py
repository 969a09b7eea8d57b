"""Objects to insert that are drawn like the loaded corpus: the same
mixture centres, the configuration's interval law, float32-exact
endpoints, from a stream of their own (``[seed, 6]``) of the configuration's
``data_seed``, so every run of a deployment inserts the same objects."""
import numpy as np

from benchmarks.chip import data


def make(cfg, writes, seed, count):
    dim = int(cfg["dim"])
    centers = data.mixture_centres(int(cfg["clusters"]), dim, seed=seed)
    rng = np.random.default_rng([seed, 6])
    x = data.from_mixture(centers, count, float(cfg["spread"]), rng)
    s, t = data.intervals_from(rng, count, cfg["intervals"])
    return x, s, t
