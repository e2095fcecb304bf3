import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax-importing import: jax locks the device count on init.

"""Multi-pod dry-run: AOT lower + compile every (arch x shape x mesh) cell.

For each cell this produces artifacts/dryrun/<arch>__<shape>__<mesh>.json with
  - per-device memory analysis (argument/output/temp bytes)
  - per-device cost analysis (HLO flops, bytes accessed)
  - collective traffic parsed from the partitioned HLO (per collective kind)
  - MODEL_FLOPS (6·N·D or 2·N·D with N_active for MoE)
which profiler/traffic.py turns into per-cell memory requirements.

Usage:
  python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--multi-pod] [--skip-existing]
"""
import argparse
import json
import re
import time
import traceback
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.compat import named_sharding
from repro.configs import SHAPES, get_config, list_archs
from repro.launch.mesh import make_production_mesh
from repro.models import LM
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.parallel.sharding import (batch_pspec_tree, cache_pspec_tree,
                                     opt_pspec_tree, param_pspec_tree, to_named)
from repro.serve.engine import make_decode_step, make_prefill_step
from repro.train.step import make_train_step

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1}

_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _bf16_legalization_bytes(hlo_text: str, min_bytes: int = 1 << 28) -> int:
    """Estimate fp32 twin buffers created by CPU bf16 legalization: for every
    large bf16 shape that also occurs as an f32 buffer, count the f32 copy."""
    shapes = set(_SHAPE_RE.findall(hlo_text))
    bf16 = {dims for dt, dims in shapes if dt == "bf16"}
    total = 0
    for dt, dims in shapes:
        if dt == "f32" and dims in bf16:
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            if n * 4 >= min_bytes:
                total += n * 4
    return total


_COND_RE = re.compile(r"condition=%?([\w.\-]+)")
_BODY_RE = re.compile(r"body=%?([\w.\-]+)")
_WHILE_RE = re.compile(r"\bwhile\(")
_CONST_RE = re.compile(r"s32\[\]\s+constant\((\d+)\)")
_REF_RE = re.compile(r"(?:to_apply|calls)=%?([\w.\-]+)")


def _split_computations(hlo_text: str):
    """name -> body lines. A computation header is a column-0 line ending in
    '{' (params may contain nested parens, so parse only the leading token)."""
    comps = {}
    cur = None
    for line in hlo_text.splitlines():
        if line and line[0] not in " \t}" and line.rstrip().endswith("{"):
            tok = line.strip()
            if tok.startswith("ENTRY"):
                tok = tok[len("ENTRY"):].strip()
            name = tok.split("(", 1)[0].split(" ", 1)[0].strip().lstrip("%")
            if name in ("HloModule",) or not name:
                cur = None
                continue
            cur = name
            comps[cur] = []
        elif cur is not None:
            comps[cur].append(line)
    return comps


def collective_stats(hlo_text: str):
    """Per-device collective traffic by kind, with `while` trip-count
    multiplication: a collective inside a scanned layer body executes
    trip-count times per step, but appears once in the HLO text. Trip counts
    are recovered from the loop-condition constants."""
    comps = _split_computations(hlo_text)

    def trip_count(cond_name: str) -> int:
        consts = [int(c) for line in comps.get(cond_name, ())
                  for c in _CONST_RE.findall(line)]
        return max(consts) if consts else 1

    from functools import lru_cache

    @lru_cache(maxsize=None)
    def totals(comp_name: str):
        stats: dict = {}
        for line in comps.get(comp_name, ()):
            m = _COLL_RE.search(line)
            if m:
                b = _shape_bytes(m.group(1))
                st = stats.setdefault(m.group(2), {"count": 0, "bytes": 0})
                st["count"] += 1
                st["bytes"] += b
            if _WHILE_RE.search(line):
                c = _COND_RE.search(line)
                b = _BODY_RE.search(line)
                if b:
                    trips = trip_count(c.group(1)) if c else 1
                    for kind, sub in totals(b.group(1)).items():
                        st = stats.setdefault(kind, {"count": 0, "bytes": 0})
                        st["count"] += sub["count"] * trips
                        st["bytes"] += sub["bytes"] * trips
                continue
            for ref in _REF_RE.findall(line):
                for kind, sub in totals(ref).items():
                    st = stats.setdefault(kind, {"count": 0, "bytes": 0})
                    st["count"] += sub["count"]
                    st["bytes"] += sub["bytes"]
        return stats

    entry = None
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY"):
            tok = line[len("ENTRY"):].strip()
            entry = tok.split("(", 1)[0].split(" ", 1)[0].strip().lstrip("%")
            break
    if entry is None or entry not in comps:
        # fallback: flat count (no trip multiplication)
        stats = {}
        for m in _COLL_RE.finditer(hlo_text):
            st = stats.setdefault(m.group(2), {"count": 0, "bytes": 0})
            st["count"] += 1
            st["bytes"] += _shape_bytes(m.group(1))
        return stats
    # deep-copy out of the lru_cache
    return json.loads(json.dumps(totals(entry)))


def model_flops_params(cfg, params_sd):
    """(N_total, N_active): parameter counts; MoE scales routed experts by
    top_k/num_experts."""
    flat = jax.tree_util.tree_flatten_with_path(params_sd)[0]
    total = 0
    expert = 0
    for path, leaf in flat:
        keys = [getattr(k, "key", getattr(k, "name", None)) for k in path]
        n = 1
        for s in leaf.shape:
            n *= s
        total += n
        if "moe" in keys and keys[-1] in ("wg", "wu", "wd") and "shared" not in keys:
            expert += n
    if cfg.moe and cfg.num_experts:
        frac = cfg.top_k / cfg.num_experts
        active = total - expert + expert * frac
    else:
        active = total
    return int(total), int(active)


def _scalar_sh(mesh):
    return named_sharding(mesh)


def build_cell(cfg, shape, mesh, opt_level: int = 0):
    """Returns (jitted_fn, arg_specs) ready for .lower().

    opt_level 0 = paper-faithful baseline sharding; >=1 enables the §Perf
    optimizations (inference param replication, shard_map MoE via
    REPRO_MOE_SHARDMAP)."""
    lm = LM(cfg)
    quant = cfg.name == "deepseek-v3-671b"
    acfg = AdamWConfig(quantized=quant)
    # shard_map MoE only applies to training (inference spreads experts over
    # model x data, where the psum("model") combine wouldn't reach them)
    if opt_level >= 2 and shape.kind == "train":
        os.environ["REPRO_MOE_SHARDMAP"] = "1"
    else:
        os.environ.pop("REPRO_MOE_SHARDMAP", None)
    params_sd = jax.eval_shape(lm.init, jax.random.key(0))
    # --opt >= 1: inference cells replicate params over "data" (no optimizer
    # state to shard -> FSDP gathering is pure waste). Baseline (--opt 0)
    # keeps the uniform train-style sharding.
    pmode = "train" if (shape.kind == "train" or opt_level < 1) else "infer"
    psh = to_named(mesh, param_pspec_tree(mesh, params_sd, mode=pmode))
    batch_sd = lm.input_specs(shape)
    bsh = to_named(mesh, batch_pspec_tree(mesh, batch_sd))

    if shape.kind == "train":
        mb = int(os.environ.get("REPRO_MICROBATCH", "0")) or None
        _, step = make_train_step(cfg, acfg=acfg, microbatch=mb)
        opt_sd = jax.eval_shape(partial(adamw_init, acfg=acfg), params_sd)
        osh = to_named(mesh, opt_pspec_tree(mesh, params_sd, opt_sd))
        f = jax.jit(step,
                    in_shardings=(psh, osh, bsh, _scalar_sh(mesh)),
                    out_shardings=(psh, osh, None),
                    donate_argnums=(0, 1))
        args = (params_sd, opt_sd, batch_sd,
                jax.ShapeDtypeStruct((), jnp.int32))
        return f, args

    if shape.kind == "prefill":
        _, prefill = make_prefill_step(cfg, max_seq=shape.seq_len)
        cache_sd, logits_sd = jax.eval_shape(prefill, params_sd, batch_sd)
        csh = to_named(mesh, cache_pspec_tree(mesh, cache_sd, cfg))
        lsh = to_named(mesh, batch_pspec_tree(mesh, logits_sd))
        f = jax.jit(prefill, in_shardings=(psh, bsh),
                    out_shardings=(csh, lsh))
        return f, (params_sd, batch_sd)

    # decode: one token against a cache of seq_len
    _, decode = make_decode_step(cfg)
    cache_sd = jax.eval_shape(
        partial(lm.init_cache, shape.global_batch, shape.seq_len))
    csh = to_named(mesh, cache_pspec_tree(mesh, cache_sd, cfg))
    logits_sd, _ = jax.eval_shape(decode, params_sd, cache_sd, batch_sd)
    lsh = to_named(mesh, batch_pspec_tree(mesh, logits_sd))
    f = jax.jit(decode, in_shardings=(psh, csh, bsh),
                out_shardings=(lsh, csh), donate_argnums=(1,))
    return f, (params_sd, cache_sd, batch_sd)


def run_cell(arch: str, shape_name: str, multi_pod: bool, outdir: Path,
             save_hlo: bool = False, opt_level: int = 0):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    out_path = outdir / f"{arch}__{shape_name}__{mesh_name}.json"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "kind": shape.kind, "seq_len": shape.seq_len,
           "global_batch": shape.global_batch}

    if shape.name == "long_500k" and not cfg.supports_long_context:
        rec["status"] = "skipped"
        rec["reason"] = "full-attention arch; long_500k requires sub-quadratic attention (DESIGN.md §4)"
        out_path.write_text(json.dumps(rec, indent=2))
        print(f"[dryrun] SKIP {arch} {shape_name} ({mesh_name})")
        return rec

    rec["opt_level"] = opt_level
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    with mesh:
        f, args = build_cell(cfg, shape, mesh, opt_level)
        lowered = f.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        ma = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_bytes_per_device": (ma.argument_size_in_bytes
                                      + ma.output_size_in_bytes
                                      + ma.temp_size_in_bytes
                                      - ma.alias_size_in_bytes),
        }
        ca = compiled.cost_analysis() or {}
        rec["cost"] = {"flops_per_device": float(ca.get("flops", 0.0)),
                       "bytes_per_device": float(ca.get("bytes accessed", 0.0))}
        hlo = compiled.as_text()
        rec["collectives"] = collective_stats(hlo)
        rec["collective_bytes_per_device"] = sum(
            v["bytes"] for v in rec["collectives"].values())
        # XLA:CPU legalizes bf16 through fp32 (no native bf16): large bf16
        # buffers acquire a same-shape fp32 twin that would NOT exist on the
        # TPU backend. Report a corrected estimate alongside the raw number.
        corr = _bf16_legalization_bytes(hlo)
        rec["memory"]["bf16_legalization_bytes"] = corr
        rec["memory"]["peak_bytes_tpu_estimate"] = max(
            0, rec["memory"]["peak_bytes_per_device"] - corr)
        if save_hlo:
            (outdir / f"{arch}__{shape_name}__{mesh_name}.hlo.txt").write_text(hlo)

    params_sd = jax.eval_shape(LM(cfg).init, jax.random.key(0))
    n_total, n_active = model_flops_params(cfg, params_sd)
    tokens = shape.global_batch * (shape.seq_len if shape.kind in ("train", "prefill")
                                   else 1)
    mult = 6 if shape.kind == "train" else 2
    rec["params_total"] = n_total
    rec["params_active"] = n_active
    rec["model_flops_global"] = mult * n_active * tokens
    rec["timing"] = {"lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2)}
    rec["status"] = "ok"
    out_path.write_text(json.dumps(rec, indent=2))
    print(f"[dryrun] OK {arch} {shape_name} ({mesh_name}) "
          f"compile={t_compile:.1f}s flops/dev={rec['cost']['flops_per_device']:.3g} "
          f"peak={rec['memory']['peak_bytes_per_device']/2**30:.2f}GiB "
          f"coll={rec['collective_bytes_per_device']/2**20:.1f}MiB")
    return rec


def memory_dse_annotate(cells, outdir: Path):
    """One ``repro.api.explore`` call over every successful cell: derive the
    GainSight-analog L1/L2 requirements from each dry-run record and stamp
    the selected heterogeneous memory mix back into its JSON."""
    from repro.api import SelectionPolicy, explore
    from repro.profiler.traffic import arch_task

    tasks, paths = [], {}
    for rec, out_path in cells:
        if rec.get("status") != "ok":
            continue
        t = arch_task(rec["arch"], rec["shape"], rec)
        tasks.append(t)
        paths[t.task_id] = (rec, out_path)
    if not tasks:
        return
    report = explore(tasks=tasks,
                     policy=SelectionPolicy(allow_refresh=True),
                     cache=outdir / "dse_cache")
    for tid, levels in report.labels().items():
        rec, out_path = paths[tid]
        rec["memory_dse"] = levels
        out_path.write_text(json.dumps(rec, indent=2))
        print(f"[dryrun] DSE {tid}: L1={levels['L1']} L2={levels['L2']}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--opt", type=int, default=0,
                    help="0=baseline sharding, >=1 perf-optimized")
    ap.add_argument("--dse", action="store_true",
                    help="annotate each compiled cell with its heterogeneous "
                         "L1/L2 memory pick (repro.api.explore)")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()
    if args.opt >= 2:
        os.environ["REPRO_MOE_SHARDMAP"] = "1"
    if args.opt >= 3:
        # refuted for prefill (see EXPERIMENTS.md §Perf): kept as an explicit
        # opt level so the negative result stays reproducible
        os.environ["REPRO_SEQ_SHARDED"] = "1"
    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)

    cells = []
    if args.all:
        for arch in list_archs():
            for shape in SHAPES:
                cells.append((arch, shape))
    else:
        cells.append((args.arch, args.shape))

    failures = 0
    done = []
    for arch, shape in cells:
        mesh_name = "pod2x16x16" if args.multi_pod else "pod16x16"
        out_path = outdir / f"{arch}__{shape}__{mesh_name}.json"
        if args.skip_existing and out_path.exists():
            rec = json.loads(out_path.read_text())
            if rec.get("status") in ("ok", "skipped"):
                done.append((rec, out_path))
                continue
        try:
            rec = run_cell(arch, shape, args.multi_pod, outdir,
                           save_hlo=args.save_hlo, opt_level=args.opt)
            done.append((rec, out_path))
        except Exception as e:  # record failure, keep sweeping
            failures += 1
            rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                   "status": "error", "error": repr(e),
                   "traceback": traceback.format_exc()[-4000:]}
            out_path.write_text(json.dumps(rec, indent=2))
            print(f"[dryrun] FAIL {arch} {shape} ({mesh_name}): {e!r}")
    if args.dse:
        memory_dse_annotate(done, outdir)
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
