"""The paper's figure and table data (Figs 7-11, Table 2), printed row by row
through the ``repro.api`` façade (Compiler / DesignTable / explore).

Each figure function returns ``(rows, derived)``: the printable rows and a
one-line summary of the claim being reproduced, which
``tests/test_core_compiler.py`` asserts. Run::

    pip install -e . && python examples/paper_figures.py
"""
import numpy as np

from repro.api import Compiler, DesignTable, MacroConfig, explore
from repro.core import bitcells, gainsight, retention

KB_SIZES = [(16, 16), (32, 32), (64, 32), (64, 64), (128, 64), (128, 128)]

_COMPILER = Compiler()


def fig7_area():
    """Fig 7: array + total bank area, dual-port GC vs single-port SRAM."""
    rows = []
    crossover_ok = []
    for wz, nw in KB_SIZES:
        r = {}
        for mt in ("sram6t", "gc_sisi", "gc_ossi"):
            c = _COMPILER.compile(mem_type=mt, word_size=wz,
                                  num_words=nw).ppa
            r[f"{mt}_array_um2"] = round(c["area_array_um2"], 1)
            r[f"{mt}_total_um2"] = round(c["area_um2"], 1)
        kb = wz * nw / 1024
        rows.append({"size_kb": kb, **r})
        if kb > 1:
            crossover_ok.append(r["gc_sisi_total_um2"] < r["sram6t_total_um2"])
        assert r["gc_ossi_total_um2"] < r["gc_sisi_total_um2"]
        assert r["gc_sisi_array_um2"] < r["sram6t_array_um2"]
    derived = (f"GC arrays always smaller; Si-Si bank < SRAM above 1Kb in "
               f"{sum(crossover_ok)}/{len(crossover_ok)} sizes; OS-Si smallest everywhere")
    return rows, derived


def fig8_speed_power():
    """Fig 8: operating frequency, effective bandwidth, leakage power."""
    rows = []
    for mt in ("sram6t", "gc_sisi", "gc_ossi"):
        for wz, nw, tag in ((128, 32, "4:1"), (64, 64, "1:1"), (32, 128, "1:4")):
            for ls in ((False, True) if mt != "sram6t" else (False,)):
                c = _COMPILER.compile(mem_type=mt, word_size=wz,
                                      num_words=nw, mux=1,
                                      level_shift=ls).ppa
                rows.append({
                    "mem": mt, "org": f"{wz}x{nw}({tag})", "ls": int(ls),
                    "f_op_mhz": round(c["f_op_hz"] / 1e6, 1),
                    "bw_gbs": round(c["bandwidth_bits_s"] / 8e9, 2),
                    "bw_total_gbs": round(c["bandwidth_total_bits_s"] / 8e9, 2),
                    "p_leak_uw": round(c["p_leak_w"] * 1e6, 4),
                })
    sram_f = max(r["f_op_mhz"] for r in rows if r["mem"] == "sram6t")
    sisi_f = max(r["f_op_mhz"] for r in rows if r["mem"] == "gc_sisi")
    ossi_f = max(r["f_op_mhz"] for r in rows if r["mem"] == "gc_ossi")
    sram_leak = np.mean([r["p_leak_uw"] for r in rows if r["mem"] == "sram6t"])
    gc_leak = np.mean([r["p_leak_uw"] for r in rows if r["mem"] != "sram6t"])
    derived = (f"f_op: SRAM {sram_f:.0f} > Si-Si {sisi_f:.0f} > OS-Si "
               f"{ossi_f:.0f} MHz; GC leakage {sram_leak/gc_leak:.0f}x below SRAM")
    return rows, derived


def fig9_retention():
    """Fig 9: retention + modulation via VT and WWLLS."""
    rows = []
    for name in ("gc_sisi", "gc_sisi_hvt", "gc_ossi", "gc_ossi_hvt",
                 "gc_osos", "gc_osos_hvt"):
        cell = bitcells.BITCELLS[name]
        for ls in (0, 1):
            rows.append({"cell": name, "ls": ls,
                         "t_ret_s": float(retention.retention_time(cell, ls)),
                         "v0": float(bitcells.sn_high_level(cell, ls))})
    by = {(r["cell"], r["ls"]): r["t_ret_s"] for r in rows}
    derived = (f"Si-Si {by[('gc_sisi',0)]:.1e}s (us-scale); OS-Si "
               f"{by[('gc_ossi',0)]:.1e}s (ms-scale); OS-OS+HVT+LS "
               f"{by[('gc_osos_hvt',1)]:.1e}s (>10s); WWLLS improves retention")
    return rows, derived


def fig10_requirements():
    """Fig 10 (reconstructed): per-task L1/L2 frequency + lifetime needs."""
    rows = []
    l2_higher = 0
    for t in gainsight.TASKS:
        f1 = max(b.f_hz for b in t.l1.buckets)
        f2 = max(b.f_hz for b in t.l2.buckets)
        l2_higher += f2 > f1
        rows.append({"task": t.task_id, "name": t.name,
                     "l1_f_ghz": round(f1 / 1e9, 2),
                     "l2_f_ghz": round(f2 / 1e9, 2),
                     "l1_lifetime_s": max(b.lifetime_s for b in t.l1.buckets),
                     "l2_lifetime_s": max(b.lifetime_s for b in t.l2.buckets)})
    derived = (f"{l2_higher}/7 tasks need higher L2 read frequency than L1 "
               f"(shared-L2 effect the paper highlights)")
    return rows, derived


def table2_optimal():
    """Table 2: optimal heterogeneous L1/L2 configuration per task."""
    report = explore(tasks=gainsight.TASKS, cache="artifacts/dse_cache")
    labels = report.labels()
    rows = []
    for t in report.tasks:
        exp = gainsight.TABLE2_EXPECTED[t.task_id]
        rows.append({"task": t.task_id, **labels[t.task_id],
                     "match": labels[t.task_id] == exp})
    matches = report.matches(gainsight.TABLE2_EXPECTED)
    derived = f"Table 2 reproduced {matches}/7 tasks exactly"
    return rows, derived


def fig11_shmoo():
    """Fig 11: single-bank Si-Si feasibility shmoo (16x16 .. 128x128)."""
    sizes = [16, 32, 64, 128]
    cfgs = [MacroConfig(mem_type="gc_sisi", word_size=wz, num_words=nw, mux=1)
            for wz in sizes for nw in sizes]
    table = DesignTable.from_configs(cfgs)
    rows = []
    for t in gainsight.TASKS:
        for lvl_name, lvl in (("L1", t.l1), ("L2", t.l2)):
            b = lvl.buckets[0]
            ok = table.shmoo(b.f_hz, b.lifetime_s)
            rows.append({"task": t.task_id, "level": lvl_name,
                         "workable": int(ok.sum()), "of": len(cfgs),
                         "grid": "".join("G" if o else "R" for o in ok)})
    n_green = sum(r["workable"] for r in rows)
    derived = f"shmoo: {n_green}/{len(rows) * len(cfgs)} green cells across 7 tasks x L1/L2"
    return rows, derived


ALL = [fig7_area, fig8_speed_power, fig9_retention, fig10_requirements,
       table2_optimal, fig11_shmoo]


def main():
    for fn in ALL:
        rows, derived = fn()
        print(f"== {fn.__name__}: {fn.__doc__.splitlines()[0]}")
        for row in rows:
            print("  " + ", ".join(f"{k}={v}" for k, v in row.items()))
        print(f"  -> {derived}\n")


if __name__ == "__main__":
    main()
