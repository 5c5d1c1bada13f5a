"""Readers of the set-up's clocks."""


def compile_s(run: dict, metric: dict):
    """Seconds the backend spent compiling during set-up
    (``jax.monitoring``'s ``backend_compile_duration``, summed). A run that
    finds every program in the persistent cache reads close to 0."""
    clock = run["compile"]
    if not clock:  # a runner that kept no compile clock
        return None
    return clock["seconds"], {"programs": clock["count"],
                              "cache_hits": clock["cache_hits"]}
