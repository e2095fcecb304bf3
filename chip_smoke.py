"""Smoke run of the DSE main path on TPU: a correctness check, not a benchmark.

It drives the path a user runs, through the public entry points
(``repro.api`` / ``repro.hetero`` / ``repro.sim``), at the sizes users run,
in one process:

  0  device check: a TPU, with the native kernel backend
  1  characterize the paper grid (120 configs) at the four named corners,
     against the golden corner slice
  2  Table 2 through explore, compose and compose(refine="simulate"); the
     vdd-sweep golden; the full (vdd x refresh-margin) sweep against its
     golden winners
  3  N-level branch-and-bound against exhaustive search
  4  trace replay of a seeded 50,000-composition grid against the
     interpret oracle
  5  the Pallas retention kernel, natively, against its jnp reference

Usage::

    python chip_smoke.py              # one chip: phases 0-5
    python chip_smoke.py --chips 4    # four chips: the sharded paths only,
                                      # each against its single-device result

Every check raises, so any failure exits non-zero. Lines tagged
``[smoke, not a benchmark]`` carry per-phase wall time (compilation
included) and the jit traces the ``repro.obs`` compile probes saw. The last
line of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
sys.path[:0] = [str(REPO / "src"), str(REPO / "scripts")]

TAG = "[smoke, not a benchmark]"


def require(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"smoke check failed: {what}")


def report_worst(name: str, worst) -> None:
    for metric, err in sorted(worst.items()):
        print(f"  {name} worst rel err {metric}: {err!r}")


def run_phase(label: str, fn, *args):
    """Run one phase with the obs tracer on; print its wall time and the
    programs it compiled or loaded from the compile cache."""
    from repro import obs
    obs.clear()
    c0 = obs.value("jax.compiles")
    t0 = time.perf_counter()
    with obs.enabled_scope(True):
        out = fn(*args)
    wall = time.perf_counter() - t0
    compiles = obs.value("jax.compiles") - c0
    print(f"{TAG} phase {label}: wall {wall!r} s, compiles {compiles}",
          flush=True)
    return out


# the replayed task: two levels of two buckets each (S = 4 slots)
REPLAY_TASK_LEVELS = (
    ("L1", 1 << 20, ((0.6, 1.2e9, 2e-6), (0.4, 5e8, 1e-4))),
    ("L2", 64 << 20, ((0.5, 1e9, 1e-3), (0.5, 2e9, 3e-6))),
)
REPLAY_PHASES = ("prefill", "decode")


def replay_grid(table, J: int, bins: int, seed: int = 0):
    """The synthetic replay problem: ``(cols, idx (J, S), traces)`` with
    uniform random table rows per slot — the same gather + scan cost profile
    as a real top-K re-rank, but with a controllable J."""
    import numpy as np

    from repro.core.select import Bucket, LevelReq, TaskReq
    from repro.sim import task_traces
    from repro.sim.rerank import sim_cols

    task = TaskReq("bench", "bench", {
        name: LevelReq(name, cap, tuple(Bucket(*b) for b in buckets))
        for name, cap, buckets in REPLAY_TASK_LEVELS})
    traces = task_traces(task, phases=REPLAY_PHASES, n_bins=bins)
    S = sum(len(b) for _, _, b in REPLAY_TASK_LEVELS)
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, len(table), size=(J, S)).astype(np.int32)
    return sim_cols(table), idx, traces


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase0_device(n_chips: int):
    import jax

    from repro.kernels import backend
    forced = os.environ.get("REPRO_KERNEL_BACKEND")
    if forced in ("interpret", "xla"):
        raise SystemExit(f"REPRO_KERNEL_BACKEND={forced} forces the {forced} "
                         f"kernel path; unset it for a chip run")
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"no TPU: jax.devices()[0].platform is "
                         f"{devices[0].platform!r}")
    if backend.resolve_backend() != "tpu":
        raise SystemExit(f"kernel backend resolves to "
                         f"{backend.resolve_backend()!r}, not 'tpu'")
    if len(devices) < n_chips:
        raise SystemExit(f"--chips {n_chips} but JAX sees {len(devices)} "
                         f"device(s)")
    print(f"device: {devices[0].device_kind} x{len(devices)} "
          f"(jax {jax.__version__})", flush=True)
    return devices[0]


def phase1_characterize(golden):
    import numpy as np

    from repro.api import Compiler
    from repro.core.corners import CORNERS
    from update_golden import characterization_rows, golden_diff

    table = Compiler().table(corners=list(CORNERS.values()))
    require(len(table) == 120, f"paper grid has {len(table)} configs")
    require(table.corner_labels == tuple(CORNERS), "corner order")
    for name, col in table.metrics.items():
        require(bool(np.all(np.isfinite(col))), f"non-finite {name}")

    def key(row):
        return (row["mem_type"], row["word_size"], row["num_words"],
                row["level_shift"])

    at = {key(table.row(i)): i for i in range(len(table))}
    # the golden slice's rows of the paper grid, every corner column
    want = golden["corners"]
    live = characterization_rows(table, [at[key(r)] for r in want])
    drift, worst = golden_diff(want, live)
    report_worst("characterization", worst)
    require(not drift, "characterization vs golden:\n  "
            + "\n  ".join(drift[:20]))
    return table


def phase2_table2(vdd_golden):
    from repro.api import compose, explore
    from repro.core import gainsight
    from repro.hetero import ComposePolicy
    from update_golden import (FULL_MARGINS, FULL_VDDS, full_sweep_tasks,
                               golden_diff, vdd_tasks)

    expected = gainsight.TABLE2_EXPECTED
    report = explore(tasks=gainsight.TASKS)
    n = report.matches(expected)
    print(f"  explore: Table 2 {n}/7")
    require(n == 7, f"explore Table 2 {n}/7")
    for refine in (None, "simulate"):
        n = sum(compose(None, t, refine=refine).matches(expected[t.task_id])
                for t in gainsight.TASKS)
        print(f"  compose(refine={refine}): Table 2 {n}/7")
        require(n == 7, f"compose(refine={refine}) Table 2 {n}/7")

    drift, worst = golden_diff(vdd_golden["tasks"], vdd_tasks())
    report_worst("vdd golden", worst)
    require(not drift, "vdd sweep vs golden:\n  " + "\n  ".join(drift[:20]))
    drift, worst = golden_diff(vdd_golden["full_sweep"], full_sweep_tasks())
    report_worst("full-sweep golden", worst)
    require(not drift, "full sweep vs golden:\n  " + "\n  ".join(drift[:20]))

    table = report.table
    kw = dict(objective="power", candidate_mode="all_feasible",
              vdd_sweep=FULL_VDDS, refresh_margin_sweep=FULL_MARGINS)
    ex = compose(table, gainsight.TASKS[0],
                 compose_policy=ComposePolicy(search="exhaustive", **kw))
    bb = compose(table, gainsight.TASKS[0],
                 compose_policy=ComposePolicy(search="branch_and_bound", **kw))
    print(f"  full sweep: {ex.n_space} compositions for task 1, B&B scored "
          f"{bb.n_compositions}")
    require(bb.labels() == ex.labels()
            and bb.best.metrics == ex.best.metrics,
            "full sweep: B&B best != exhaustive best")
    return table


def phase3_nlevel(table):
    from repro.api import compose
    from repro.core.gainsight import nlevel_task
    from repro.hetero import ComposePolicy

    kw = dict(objective="power", candidate_mode="all_feasible",
              max_candidates_per_bucket=16)
    ex = compose(table, nlevel_task(4), compose_policy=ComposePolicy(
        search="exhaustive", max_compositions=50_000, **kw))
    bb = compose(table, nlevel_task(4), compose_policy=ComposePolicy(
        search="branch_and_bound", **kw))
    print(f"  nlevel4: exhaustive scored {ex.n_compositions}, B&B "
          f"{bb.n_compositions}")
    require(bb.labels() == ex.labels(), "nlevel4: B&B best != exhaustive")
    b5 = compose(table, nlevel_task(5), compose_policy=ComposePolicy(
        search="branch_and_bound", **kw))
    print(f"  nlevel5: B&B scored {b5.n_compositions} of {b5.n_space}")
    require(b5.search == "branch_and_bound" and b5.best.feasible,
            "nlevel5: B&B found no feasible composition")


def phase4_replay(table):
    import numpy as np

    from repro.sim import simulate_traces
    from repro.sim.engine import SIM_METRICS

    cols, idx, traces = replay_grid(table, J=50_000, bins=32)
    require(idx.shape == (50_000, 4), f"replay grid {idx.shape}")
    out = simulate_traces(cols, idx, traces, backend="xla")
    oracle = simulate_traces(cols, idx[:64], traces, backend="interpret")
    for m in SIM_METRICS:
        require(bool(np.all(np.isfinite(out[m]))), f"replay: non-finite {m}")
        require(np.array_equal(out[m][:64], oracle[m]),
                f"replay {m}: xla rows 0-63 != interpret oracle (max abs "
                f"diff {np.max(np.abs(out[m][:64] - oracle[m]))!r})")
    print(f"  replay: {idx.shape[0]} compositions x {idx.shape[1]} slots x "
          f"{traces[0].n_bins} bins x {len(traces)} phases; rows 0-63 equal "
          f"the interpret oracle bit for bit")


def phase5_retention():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import obs
    from repro.api import design_space
    from repro.core.retention import pack_cell, time_grid
    from repro.kernels import ops, ref
    from repro.kernels.retention_kernel import retention_pallas
    from update_golden import FLOAT_RTOL

    cells = [(c.mem_type, int(c.level_shift)) for c in design_space()
             if c.mem_type != "sram6t"]
    packed = {cell: pack_cell(*cell) for cell in sorted(set(cells))}
    params = jnp.asarray([packed[c] for c in cells], jnp.float32)
    ts = time_grid()
    hlo = jax.jit(retention_pallas).lower(params, ts).as_text()
    require("tpu_custom_call" in hlo, "retention_pallas lowered without a "
            "Mosaic kernel")
    n0 = obs.value("kernels.dispatch.retention.tpu")
    got = np.asarray(ops.retention_batch(params, ts), np.float64)
    require(obs.value("kernels.dispatch.retention.tpu") == n0 + 1,
            "retention did not dispatch to the tpu backend")
    want = np.asarray(ref.retention_ref(params, ts), np.float64)
    rel = float(np.max(np.abs(got - want) / np.abs(want)))
    print(f"  retention kernel: {len(cells)} gain-cell rows, worst rel err "
          f"vs retention_ref {rel!r}")
    require(rel <= FLOAT_RTOL, f"retention kernel rel err {rel} > "
            f"{FLOAT_RTOL}")


def phase_sharded():
    """Four chips: each sharded path against its single-device result."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.api import Compiler, SelectionPolicy, compose
    from repro.core import gainsight
    from repro.core.corners import CORNERS
    from repro.hetero.candidates import level_candidates
    from repro.hetero.compose import _composition_grid
    from repro.hetero.system import (METRIC_COLS, _score_corners_jit,
                                     _score_jit, score_grid,
                                     score_grid_corners)
    from repro.parallel.grid import _factor_devices, shard2d, shard_leading

    devices = jax.devices()
    table = Compiler().table(corners=list(CORNERS.values()))

    # the full nlevel_task(4) space under the benchmark's candidate kwargs
    task = gainsight.nlevel_task(4)
    slots = [bc for level in task.levels.values()
             for bc in level_candidates(table.metrics, table.families, level,
                                        SelectionPolicy(),
                                        mode="all_feasible",
                                        max_per_bucket=16, order_by="power")]
    n_space = math.prod(len(bc.candidates) for bc in slots)
    idx, _, _, _ = _composition_grid(slots, n_space)
    cap_bits = np.array([bc.capacity_bits for bc in slots], np.float64)
    f_req = np.array([bc.bucket.f_hz for bc in slots], np.float64)
    print(f"  nlevel4 space: {idx.shape[0]} compositions x {idx.shape[1]} "
          f"slots")

    single = score_grid(table.metrics, idx, cap_bits, f_req)
    split = score_grid(table.metrics, idx, cap_bits, f_req, sharded=True)
    for m, v in single.items():
        require(np.array_equal(v, split[m]), f"score_grid sharded {m}")
    print("  score_grid: sharded == single device")

    def placed(label, out):
        """Where the work landed: the output shards of each device. Returns
        the shard shapes."""
        shards = out["p_w"].addressable_shards
        on = sorted({s.device.id for s in shards})
        shapes = sorted({s.data.shape for s in shards})
        print(f"  {label}: p_w shards on devices {on}, shard shapes {shapes}")
        require(on == sorted(d.id for d in devices),
                f"{label}: work on devices {on}, not all {len(devices)}")
        return shapes

    args = (jnp.asarray(cap_bits, jnp.float32),
            jnp.asarray(f_req, jnp.float32))
    placed("shard_leading", shard_leading(
        _score_jit, jnp.asarray(idx),
        {k: jnp.asarray(np.asarray(table.metrics[k]), jnp.float32)
         for k in METRIC_COLS}, *args))

    # 4 corners on 4 chips make a 1x4 (compositions x corners) mesh; 2
    # corners make a 2x2 one, which splits the composition axis as well
    for labels in (table.corner_labels, table.corner_labels[:2]):
        per_corner = [table.corner_metrics(c) for c in labels]
        single = score_grid_corners(per_corner, idx, cap_bits, f_req)
        split = score_grid_corners(per_corner, idx, cap_bits, f_req,
                                   sharded=True)
        for m, v in single.items():
            require(np.array_equal(v, split[m]),
                    f"score_grid_corners sharded {m}, corners {labels}")
        print(f"  score_grid_corners: corners {list(labels)}, sharded == "
              f"single device")
        ways_j, ways_c = _factor_devices(len(devices), len(labels))
        shapes = placed(f"shard2d {ways_j}x{ways_c}", shard2d(
            _score_corners_jit, jnp.asarray(idx),
            {k: jnp.asarray(np.stack([np.asarray(m[k]) for m in per_corner]),
                            jnp.float32) for k in METRIC_COLS}, *args))
        want = (-(-len(labels) // ways_c), -(-idx.shape[0] // ways_j))
        require(shapes == [want], f"shard2d {ways_j}x{ways_c}: shard shapes "
                f"{shapes}, expected {[want]}")
    require(_factor_devices(len(devices), 2) == (2, 2),
            "the 2-corner case did not factor onto a 2x2 mesh")

    for t in gainsight.TASKS:
        a = compose(None, t)
        b = compose(None, t, sharded=True)
        diff = [(k, ca.metrics[k], cb.metrics[k])
                for ca, cb in zip(a.ranked, b.ranked) for k in ca.metrics
                if ca.metrics[k] != cb.metrics[k]]
        require(a.labels() == b.labels() and not diff,
                f"compose sharded, task {t.task_id}: labels {a.labels()} / "
                f"{b.labels()}, metrics (name, single, sharded) {diff[:5]}")
        require(b.matches(gainsight.TABLE2_EXPECTED[t.task_id]),
                f"compose sharded, task {t.task_id}: Table 2 mismatch")
    print("  compose(sharded=True): 7/7 Table 2, equal to single device")


# ---------------------------------------------------------------------------


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded paths, on four chips")
    args = ap.parse_args(argv)

    device = phase0_device(args.chips)
    from repro import compile_cache
    print(f"compile cache: {compile_cache.enable()}", flush=True)

    if args.chips == 4:
        run_phase("sharded (4 chips)", phase_sharded)
    else:
        goldens = REPO / "tests" / "golden"
        golden = json.loads((goldens / "table2.json").read_text())
        vdd_golden = json.loads((goldens / "table2_vdd.json").read_text())
        run_phase("1 characterize", phase1_characterize, golden)
        table = run_phase("2 table2", phase2_table2, vdd_golden)
        run_phase("3 nlevel", phase3_nlevel, table)
        run_phase("4 replay", phase4_replay, table)
        run_phase("5 retention kernel", phase5_retention)

    import jax
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
