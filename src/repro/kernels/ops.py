"""Public kernel entry points, dispatched through ``repro.kernels.backend``.

On TPU the pallas_call path runs natively; on CPU (including the 512-device
dry-run) the pure-jnp oracle runs instead so the AOT compile stays
tractable.  Backend selection is centralized in ``backend.resolve_backend``
(``REPRO_KERNEL_BACKEND`` env var, else device-based).
"""
from __future__ import annotations

import functools

import jax.numpy as jnp

from repro.kernels import backend as _backend
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention as _flash_pallas
from repro.kernels.retention_kernel import retention_pallas
from repro.kernels.ssm_scan import ssm_scan_pallas


def _ssm_scan_ref(x, dt, A, Bc, Cc, D):
    h0 = jnp.zeros((x.shape[0], A.shape[0], A.shape[1]), jnp.float32)
    return ref.ssm_scan_ref(x, dt, A, Bc, Cc, D, h0)[0]


_backend.register(
    "attention",
    tpu=_flash_pallas,
    interpret=functools.partial(_flash_pallas, interpret=True),
    xla=ref.attention_ref,
)
_backend.register(
    "ssm_scan",
    tpu=ssm_scan_pallas,
    interpret=functools.partial(ssm_scan_pallas, interpret=True),
    xla=_ssm_scan_ref,
)
_backend.register(
    "retention",
    tpu=retention_pallas,
    interpret=functools.partial(retention_pallas, interpret=True),
    xla=ref.retention_ref,
)


def attention(q, k, v, *, causal=True, backend=None):
    return _backend.dispatch("attention", q, k, v, causal=causal,
                             backend=backend)


def ssm_scan(x, dt, A, Bc, Cc, D, *, backend=None):
    return _backend.dispatch("ssm_scan", x, dt, A, Bc, Cc, D, backend=backend)


def retention_batch(params, ts, *, backend=None):
    return _backend.dispatch("retention", params, ts, backend=backend)
