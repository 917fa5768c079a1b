"""Device time per decode step of the ops under the ``kv_blocks`` scope
(layers._chunked_attention's loop over the cache blocks that hold tokens,
nested under ``attention``: each block's key and value products, masks and
online softmax). Over ``decode.attention_ms`` it is the part that follows
the cache's live length; a program without the scope reads nothing."""
from benchmarks.chip import scopes


def read(run):
    a = scopes.for_run(run, "decode")
    return a.ms("kv_blocks") if a else None
