"""The observability catalog: every span and metric name, as pure data.

Stdlib-only and free of intra-package imports on purpose — like
``repro.analysis.rules`` this file is loaded standalone via importlib by
``scripts/check_docs.py`` and the DC04 analyzer rule, which require every
name below to be documented in ``docs/OBSERVABILITY.md``. Instrumented
modules do NOT import this file; it is the audit surface, not the API.

``kernels.dispatch.<op>.<backend>`` is a *pattern* entry: the dispatch
counter family is keyed per (op, backend) pair at runtime and ``covers()``
matches any concrete name against it.
"""
from __future__ import annotations

# span name -> (where it is emitted, what it measures)
SPANS = {
    "api.compile": ("repro.api.Compiler.compile",
                    "single-macro characterization (one config, no vmap)"),
    "api.encode": ("repro.api.DesignTable.from_configs, "
                   "repro.hetero.expand.expand_metrics",
                   "config-to-vector encoding of a design space (one (N, 7) "
                   "host array and one device put), before api.characterize "
                   "and once per expand call inside hetero.expand"),
    "api.characterize": ("repro.api.DesignTable.from_configs",
                         "vmap characterization sweep over the config grid "
                         "(nominal or corner-batched), up to its columns on "
                         "the host"),
    "api.table_build": ("repro.api.DesignTable.build",
                        "table construction incl. the npz cache consult"),
    "api.explore": ("repro.api.explore",
                    "independent per-level DSE over all tasks"),
    "hetero.compose": ("repro.hetero.compose.compose",
                       "one joint composition call end to end "
                       "(cache consult, candidates, search, materialize)"),
    "hetero.search": ("repro.hetero.compose.compose",
                      "the grid ranking stage: exhaustive cross-product or "
                      "branch-and-bound enumeration"),
    "hetero.expand": ("repro.hetero.compose.compose",
                      "operating-point expansion: per-(vdd point x refresh "
                      "margin) metric blocks for the vdd_sweep search axis"),
    "hetero.score": ("repro.hetero.system.score_grid[_corners]",
                     "one batched composition-scoring dispatch, up to its "
                     "columns on the host"),
    "sim.prepare": ("repro.sim.engine.simulate_traces",
                    "replay staging: gathering the compositions' table "
                    "columns and the slot arrays, before sim.replay"),
    "sim.replay": ("repro.sim.engine.simulate_traces",
                   "batched trace replay over all phases of one call"),
    "sim.replay_phase": ("repro.sim.engine.simulate_traces",
                         "one phase's vmapped scan dispatch, up to its "
                         "metrics on the host"),
    "sim.rerank": ("repro.sim.rerank.simulate_report",
                   "simulate-then-rerank refinement incl. the sim cache "
                   "consult"),
    "parallel.shard": ("repro.parallel.grid.shard_leading/shard2d",
                       "device-mesh setup + sharded dispatch (multi-device "
                       "hosts only; single-device calls are plain)"),
    "serve.prefill": ("repro.serve.engine.Engine.generate",
                      "the prefill dispatch of one generate() call"),
    "serve.sample": ("repro.serve.engine.Engine.generate",
                     "host-side token sampling for one decode step"),
    "serve.decode_step": ("repro.serve.engine.Engine.generate",
                          "one decode step's model decode dispatch (sampling "
                          "and the host sync are outside this span)"),
}

# metric name -> (kind, what it counts/measures)
METRICS = {
    "jax.compiles": (
        "counter", "programs compiled or loaded from the persistent cache, "
        "any jit (a jax.monitoring listener, "
        "repro.compile_cache.count_compiles); every span's compiles arg"),
    "device.fetches": (
        "counter", "device-to-host transfers on the DSE path "
        "(repro.transfer.fetch, one array; fetch_tree, a whole pytree); "
        "every span's fetches arg"),
    "device.puts": (
        "counter", "host arrays sent to the device on the DSE path "
        "(repro.transfer.put); every span's puts arg"),
    "api.characterize_calls": (
        "counter", "vmap characterization sweeps executed "
        "(backs api.characterize_call_count — cache hits leave it flat)"),
    "api.table_cache_hits": (
        "counter", "DesignTable.build npz cache hits"),
    "api.table_cache_misses": (
        "counter", "DesignTable.build npz cache misses (cache consulted, "
        "table re-characterized)"),
    "hetero.compose_evals": (
        "counter", "batched composition scoring sweeps "
        "(backs hetero.composition_eval_count)"),
    "hetero.cache_hits": (
        "counter", "composition-report npz cache hits in compose()"),
    "hetero.cache_misses": (
        "counter", "composition-report npz cache misses in compose()"),
    "hetero.search_nodes": (
        "counter", "lattice nodes actually scored by branch_and_bound"),
    "hetero.search_batches": (
        "counter", "fixed-shape scoring batches branch_and_bound flushed"),
    "hetero.search_pruned": (
        "counter", "compositions proven prunable by the bound "
        "(full cross-product size minus nodes scored)"),
    "hetero.expanded_points": (
        "counter", "virtual (operating point x refresh margin) metric "
        "blocks built for vdd_sweep/refresh_margin_sweep searches"),
    "sim.replay_calls": (
        "counter", "batched trace-replay sweeps "
        "(backs sim.sim_eval_count — a sim-cache hit leaves it flat)"),
    "sim.cache_hits": (
        "counter", "sim-report npz cache hits in simulate_report()"),
    "sim.cache_misses": (
        "counter", "sim-report npz cache misses in simulate_report()"),
    "kernels.dispatch.<op>.<backend>": (
        "counter", "kernel-registry dispatches per (op, resolved backend), "
        "e.g. kernels.dispatch.sim_replay.xla"),
    "parallel.shard_calls": (
        "counter", "sharded (multi-device) grid dispatches"),
    "serve.prefill_calls": (
        "counter", "Engine.generate prefill dispatches"),
    "serve.decode_steps": (
        "counter", "Engine.generate decode steps"),
    "serve.prefill_s": (
        "histogram", "wall time of each prefill dispatch [s]"),
    "serve.decode_step_s": (
        "histogram", "wall time of each decode step's model dispatch [s]"),
    "serve.sample_s": (
        "histogram", "wall time of host-side sampling per decode step [s]"),
}


def covers(name: str) -> bool:
    """Is a concrete runtime span/metric name covered by the catalog?
    Exact entries match literally; entries containing ``<`` are prefix
    patterns (everything before the first ``<`` must prefix ``name``)."""
    if name in SPANS or name in METRICS:
        return True
    for entry in (*SPANS, *METRICS):
        head = entry.split("<", 1)[0]
        if "<" in entry and name.startswith(head):
            return True
    return False
