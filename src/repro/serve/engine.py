"""Serving: prefill/decode step factories + a minimal batched engine.

The step factories are what the dry-run lowers for the ``prefill_*`` /
``decode_*`` / ``long_*`` cells; the Engine is the runnable CPU-scale
serving loop used by examples/serve_lm.py.
"""
from __future__ import annotations

import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache, obs
from repro.models import LM

# per-step serving telemetry (repro.obs): dispatch counts always, wall-time
# histograms [s] + spans when tracing is enabled
_C_PREFILL = obs.counter("serve.prefill_calls")
_C_DECODE = obs.counter("serve.decode_steps")
_H_PREFILL_S = obs.histogram("serve.prefill_s")
_H_DECODE_S = obs.histogram("serve.decode_step_s")
_H_SAMPLE_S = obs.histogram("serve.sample_s")
# the spans' ``compiles`` arg shows which generate() call compiled
compile_cache.count_compiles()


def make_prefill_step(cfg, max_seq: Optional[int] = None):
    lm = LM(cfg)

    def prefill(params, batch):
        return lm.prefill(params, batch, max_seq=max_seq)

    return lm, prefill


def make_decode_step(cfg):
    lm = LM(cfg)

    def decode(params, cache, batch):
        logits, cache = lm.decode(params, cache, batch)
        return logits, cache

    return lm, decode


def sample_greedy(logits):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def sample_temperature(key, logits, temperature=0.8):
    return jax.random.categorical(key, logits.astype(jnp.float32) / temperature,
                                  axis=-1).astype(jnp.int32)


class Engine:
    """Batched greedy/temperature generation (CPU-scale reference loop)."""

    def __init__(self, cfg, params, max_seq=256):
        self.cfg = cfg
        self.params = params
        self.max_seq = max_seq
        self.lm, prefill = make_prefill_step(cfg, max_seq=max_seq)
        _, decode = make_decode_step(cfg)
        self._prefill = jax.jit(prefill)
        self._decode = jax.jit(decode)

    def generate(self, batch: Dict[str, Any], steps: int, temperature=None,
                 seed=0):
        t0 = time.perf_counter()
        with obs.span("serve.prefill",
                      batch=int(jax.tree.leaves(batch)[0].shape[0])):
            cache, logits = self._prefill(self.params, batch)
        _C_PREFILL.inc()
        _H_PREFILL_S.observe(time.perf_counter() - t0)
        key = jax.random.key(seed)
        outs = []
        cond = batch.get("cond")
        for i in range(steps):
            # sampling is its own span/histogram: the decode span measures
            # only the model decode dispatch, not the sampler or the
            # np.asarray(tok) host sync that lands between them
            t0 = time.perf_counter()
            with obs.span("serve.sample", step=i):
                if temperature is None:
                    tok = sample_greedy(logits)
                else:
                    key, sk = jax.random.split(key)
                    tok = sample_temperature(sk, logits, temperature)
            _H_SAMPLE_S.observe(time.perf_counter() - t0)
            outs.append(np.asarray(tok))  # host sync, outside both spans
            dec_batch = {"tokens": tok}
            if cond is not None:
                dec_batch["cond"] = cond
            t0 = time.perf_counter()
            with obs.span("serve.decode_step", step=i):
                logits, cache = self._decode(self.params, cache, dec_batch)
            _C_DECODE.inc()
            _H_DECODE_S.observe(time.perf_counter() - t0)
        return np.stack(outs, axis=1)  # (B, steps[, nq])
