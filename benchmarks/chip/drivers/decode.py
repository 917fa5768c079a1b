"""Window loop of the serving cells: a closed loop with one batch in
flight, standing for ``launch/serve.py``. Each round prefills one batch of
prompts that share a length with ``build_serve_fns``'s prefill, then decodes
greedily through the cache until the round's longest request is served,
fetching every step's tokens to the host as a streaming server does. A
request is served its own number of tokens (``inputs.serve_rounds``); the
batch keeps stepping its finished rows, as a server without a scheduler
does. The rollout is committed through the ``ControlPlane`` in set-up, and
each round's cache is released before the next prefill.

``serve_tokens_per_s``: tokens delivered to the host in the window, each
request's own tokens only (the prefill's first included), over the window.
``tpot_p95_ms``: the 95th percentile over every decode step of the window of
the time from dispatch until that step's tokens are on the host.

Once the window has closed, the round in flight is served to its end
(untimed), and a sample of the requests drawn from the seed, the longest
among them, goes to the reference: the widest gap by which a served token's
logit lies below the reference's best.
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import arch, compare, inputs, trace
from benchmarks.chip.reference import runs


def build(run):
    from repro.launch.mesh import make_host_mesh
    from repro.models import zoo
    from repro.runtime import spmd
    from repro.runtime.controlplane import ControlPlane

    cell, seed = run.cell, inputs.seed32(run.seed)
    wl, tf = cell.workload, cell.traffic
    a = arch.arch_config(wl["config"], cell.config)
    control = ControlPlane(n_nodes=wl["control_plane_nodes"], seed=seed)
    rolled = control.rollout(f"{a.name}@{seed}")
    dtype = arch.DTYPES[cell.config["torch_dtype"]]
    model = zoo.build(a, dtype=dtype)
    params = inputs.make_params(cell.config, seed, dtype)
    prefill, decode = spmd.build_serve_fns(model, make_host_mesh(run.devices[:1]), tf["max_len"])
    return params, prefill, decode, rolled


WARMUP_ROUND = 1 << 30
# A traced run traces the window's first seconds only: a decode step runs
# some 5,000 operations, and a whole window of them would make a trace of
# hundreds of MB.
TRACE_S = 3.0


@jax.jit
def _sample(logits):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]


class Round:
    """One batch of requests in flight: ``step()`` decodes one token for
    every row; ``out`` holds the tokens on the host, one (B, 1) a step."""

    def __init__(self, params, prefill, decode, prompt, out_lens):
        self.params, self.decode = params, decode
        self.prompt, self.out_lens = prompt, np.asarray(out_lens)
        with jax.profiler.TraceAnnotation("bench.prefill"):
            logits, self.cache = prefill(params, {"tokens": jnp.asarray(prompt)})
            self.tok = _sample(logits)
            self.out = [np.asarray(self.tok)]

    @property
    def done(self) -> bool:
        return len(self.out) >= int(self.out_lens.max())

    def step(self) -> None:
        with jax.profiler.TraceAnnotation("bench.decode"):
            logits, self.cache = self.decode(self.params, self.cache, {"tokens": self.tok})
            self.tok = _sample(logits)
            self.out.append(np.asarray(self.tok))

    def finish(self) -> np.ndarray:
        """Serve the round to its end, release its cache and return the
        tokens served, (B, longest request)."""
        while not self.done:
            self.step()
        self.cache = self.tok = None
        return np.concatenate(self.out, axis=1)

    def delivered(self) -> int:
        """Tokens delivered so far, each request's own only."""
        return int(np.minimum(self.out_lens, len(self.out)).sum())

    def requests(self, served: np.ndarray) -> list:
        """(prompt, served tokens) of each request of a finished round."""
        return [(self.prompt[b], served[b, :n]) for b, n in enumerate(self.out_lens)]


def new_round(run, params, prefill, decode, r: int) -> Round:
    """Round ``r`` of the cell's traffic, its cycle repeated."""
    tf, seed = run.cell.traffic, inputs.seed32(run.seed)
    p, outs = inputs.serve_rounds(tf, seed)[r % tf["rounds"]]
    prompt = inputs.prompts(r, batch=tf["batch"], prompt_len=p,
                            vocab=run.cell.config["vocab_size"], seed=seed)
    return Round(params, prefill, decode, prompt, outs)


def run(run):
    cell, seed = run.cell, inputs.seed32(run.seed)
    wl, tf, cfg = cell.workload, cell.traffic, cell.config
    B, V = tf["batch"], cfg["vocab_size"]
    params, prefill, decode, rolled = build(run)

    # Warm-up: the cell's shapes only (a prefill of each prompt length, two
    # decode steps), on prompts that no round of the window serves.
    for p in sorted({p for p, _ in inputs.serve_rounds(tf, seed)}):
        warm = Round(params, prefill, decode,
                     inputs.prompts(WARMUP_ROUND, batch=B, prompt_len=p, vocab=V, seed=seed),
                     [3] * B)
        warm.finish()
    del warm
    gc.collect()

    run.setup_done()
    tpot, rounds, kv = [], [], []
    with trace.Window(run, TRACE_S) as window:
        t0 = time.perf_counter()
        deadline = t0 + run.seconds
        while time.perf_counter() < deadline:
            rd = new_round(run, params, prefill, decode, len(rounds))
            rounds.append(rd)
            P = rd.prompt.shape[1]
            while not rd.done and time.perf_counter() < deadline:
                live = int((rd.out_lens > len(rd.out)).sum())
                s0 = time.perf_counter()
                rd.step()
                tpot.append(time.perf_counter() - s0)
                # live cache of the step, the new token included, and the
                # requests still being served
                kv.append((P + len(rd.out) - 1, live))
                window.tick()
            if not rd.done:
                break
            rd.finish()
        wall = time.perf_counter() - t0
        delivered = sum(rd.delivered() for rd in rounds)
    run.read_memory_peak()
    run.e2e["serve_tokens_per_s"] = delivered / wall
    run.e2e["tpot_p95_ms"] = float(np.percentile(np.asarray(tpot) * 1e3, 95)) if tpot else float("nan")
    run.attempted = B * len(rounds)
    done = [req for rd in rounds for req in rd.requests(rd.finish())]
    run.failed = sum(int(np.any((s < 0) | (s >= V))) for _, s in done)
    run.facts.update(decode_steps=kv)
    del params, rounds
    gc.collect()

    gaps = runs.serve_gaps(cfg, seed, sample(run, done))
    run.checks = {"logit_gap": compare.check(max(gaps), wl["limits"]["logit_gap"]),
                  "no_rollout": compare.check(0 if rolled else 1, 0)}


def sample(run, done):
    """The finished requests that go to the reference: ``check_requests`` of
    them, drawn from the seed, the longest (prompt and served tokens) among
    them."""
    n = min(run.cell.workload["check_requests"], len(done))
    size = [len(p) + len(s) for p, s in done]
    longest = max(range(len(done)), key=size.__getitem__)
    rest = [i for i in range(len(done)) if i != longest]
    pick = np.random.default_rng([inputs.seed32(run.seed), 1]).choice(len(rest), size=n - 1, replace=False)
    return [done[i] for i in sorted([longest] + [rest[j] for j in pick])]
