"""Share of the window's host time spent in one layer, in percent.

Each stack sample of the window goes to the innermost frame that any of
the cell's host-share metrics lists in its ``functions`` map, and so to
that metric's layer; where two maps list the same frame, the more
specific pattern wins (see ``diana_bench.sampler.matches``). A sample
with no listed frame goes to none. The share is the sampled time of the
layer over all sampled time. No samples, no value.
"""
from diana_bench.sampler import matches


def owner(stack, maps: dict) -> str | None:
    for key in stack:
        best, who = 0, None
        for name, patterns in maps.items():
            score = max(matches(key, p) for p in patterns)
            if score > best:
                best, who = score, name
        if who is not None:
            return who
    return None


def read(metric: dict, obs):
    if not obs.samples:
        return None
    cache: dict = {}
    hit = total = 0.0
    for weight, stack in obs.samples:
        if stack not in cache:
            cache[stack] = owner(stack, obs.layer_maps)
        total += weight
        if cache[stack] == metric["name"]:
            hit += weight
    return 100.0 * hit / total if total > 0 else None
