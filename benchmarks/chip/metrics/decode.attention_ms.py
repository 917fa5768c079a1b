"""Device time per decode step of the ops under the ``attention`` scope
(layers.attention: the q/k/v projections, RoPE, attention over every cache
slot and the output projection), leaving out ``kv_update``
(``decode.kv_cache_ms``)."""
from benchmarks.chip import scopes


def read(run):
    a = scopes.for_run(run, "decode")
    return a.ms("attention", minus="kv_update") if a else None
