"""A statistic over a list the runner kept: `args.key`, `args.stat`
('mean', 'median' or 'pNN'), times `args.scale`."""

import numpy as np


def read(args, env):
    xs = env.samples.get(args["key"])
    if not xs:
        return None
    stat = args["stat"]
    if stat == "mean":
        v = np.mean(xs)
    elif stat == "median":
        v = np.median(xs)
    elif stat.startswith("p"):
        v = np.percentile(xs, float(stat[1:]))
    else:
        raise ValueError(f"unknown statistic {stat!r}")
    return float(v) * args.get("scale", 1.0)
