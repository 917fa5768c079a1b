"""Device time per decode step of the ops under the ``kv_update`` scope
(layers.attention's write of the new token's K and V into the cache), with
the moves of the whole cache that carry no scope of their own (the layer
scan's slicing and stacking of the cache, XLA's copies of it), which take
the scope of the data they move."""
from benchmarks.chip import scopes


def read(run):
    a = scopes.for_run(run, "decode")
    return a.ms("kv_update") if a else None
