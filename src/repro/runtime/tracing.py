"""Spans and scopes on the profiler's clock.

Host spans (``span``, ``step_span``) are ``jax.profiler`` annotations: they
record only while a profiler runs and land on the host plane of its trace,
beside the device's operations. Device scopes (``scope``) are
``jax.named_scope``: compile-time metadata that puts the scope into the op
name of every HLO instruction traced under it, at no cost when the program
runs. Nothing else records or exports them.
"""
from __future__ import annotations

import jax

PREFIX = "repro."

# Host spans, each ``repro.<name>``: one ``train.step`` per trainer step, the
# step's phases as its children, and the loop's rarer host work.
SPANS = ("train.step", "train.data", "train.place", "train.dispatch", "train.sync",
         "train.straggler", "train.ckpt", "train.init", "control.commit")

# Device scopes. Train step: forward and backward, the Fast Raft vote, the
# gradient reduction, clipping, AdamW and the quorum gate. Models: the
# embedding, attention (with the cache write), the FFN and the LM head.
SCOPES = ("train/fwd_bwd", "train/vote", "train/reduce", "train/clip", "train/adamw",
          "train/gate", "embed", "attention", "kv_update", "ffn", "head")

# Device scopes nested inside one of SCOPES, naming part of its time:
# ``kv_blocks``, attention's loop over the cache blocks that hold tokens.
INNER_SCOPES = ("kv_blocks",)


def span(name: str, **ids) -> jax.profiler.TraceAnnotation:
    """Host span ``repro.<name>``, with ``ids`` as its arguments."""
    assert name in SPANS, name
    return jax.profiler.TraceAnnotation(PREFIX + name, **ids)


def step_span(step: int) -> jax.profiler.StepTraceAnnotation:
    """The parent span of one trainer step: its children share its step."""
    return jax.profiler.StepTraceAnnotation(PREFIX + "train.step", step_num=step)


def scope(name: str):
    """Name scope for device code, ``name`` one of ``SCOPES`` or ``INNER_SCOPES``."""
    assert name in SCOPES + INNER_SCOPES, name
    return jax.named_scope(name)
