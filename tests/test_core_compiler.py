"""OpenGCRAM core: device/retention physics, macro PPA trends, DSE,
artifacts — unit + hypothesis property tests."""
import jax.numpy as jnp
import numpy as np
import pytest

# hypothesis is a dev extra: property tests skip where absent, unit tests run
from _hypothesis_compat import given, settings, st

from repro.api import DesignTable, design_space, gradient_size_macro
from repro.core import bitcells, devices, gainsight, retention, select, tech
from repro.core.artifacts import emit_lef, emit_lib, emit_verilog, generate_all
from repro.core.characterize import characterize_config
from repro.core.macro import MacroConfig

settings.register_profile("ci", max_examples=25, deadline=None)
settings.load_profile("ci")


# ---------------------------------------------------------------------- devices
def test_device_ion_calibration():
    assert abs(float(devices.i_on(devices.SI_NMOS, 1.0)) - 600e-6) / 600e-6 < 0.01
    assert abs(float(devices.i_on(devices.ITO_OS, 1.0)) - 110e-6) / 110e-6 < 0.01


def test_os_off_current_orders_below_si():
    i_si = float(devices.i_off(devices.SI_NMOS, 1.0))
    i_os = float(devices.i_off(devices.ITO_OS_HVT, 1.0))
    assert i_os < 1e-4 * i_si          # orders of magnitude (paper: <1e-18 A/um)


@given(vgs=st.floats(0.0, 1.1), vds=st.floats(0.05, 1.1))
def test_device_current_monotone_in_vgs(vgs, vds):
    i1 = float(devices.mosfet_id(devices.SI_NMOS, vgs, vds, 1.0))
    i2 = float(devices.mosfet_id(devices.SI_NMOS, vgs + 0.05, vds, 1.0))
    assert i2 >= i1 * (1 - 1e-6)


# -------------------------------------------------------------------- retention
def test_retention_ordering_matches_paper():
    """Fig 9: Si-Si microseconds < OS-Si milliseconds < OS-OS(+HVT) > 10 s."""
    t_sisi = float(retention.retention_time(bitcells.BITCELLS["gc_sisi"], 0))
    t_ossi = float(retention.retention_time(bitcells.BITCELLS["gc_ossi"], 0))
    t_osos_hvt = float(retention.retention_time(
        bitcells.BITCELLS["gc_osos_hvt"], 1))
    assert 1e-7 < t_sisi < 1e-4          # microseconds
    assert 1e-4 < t_ossi < 1.0           # millisecond-level
    assert t_osos_hvt > 10.0             # ">10 s with VT engineering"
    assert t_sisi < t_ossi < t_osos_hvt


def test_level_shifter_improves_retention():
    for name in ("gc_sisi", "gc_ossi", "gc_osos"):
        c = bitcells.BITCELLS[name]
        assert float(retention.retention_time(c, 1)) > \
            float(retention.retention_time(c, 0))


def test_transient_matches_estimate_within_grid():
    """The RK4 solve should land within ~1 order of the closed-form C*dV/I."""
    for name in ("gc_sisi", "gc_ossi", "gc_osos"):
        c = bitcells.BITCELLS[name]
        t = float(retention.retention_time(c, 0))
        est = float(retention.retention_estimate(c, 0))
        assert 0.1 < t / est < 10.0


# ------------------------------------------------------------------------ macro
def test_bitcell_area_ratios_match_paper():
    a_sram = tech.SRAM6T_W * tech.SRAM6T_H
    assert abs(tech.GC_SISI_W * tech.GC_SISI_H / a_sram - 0.69) < 0.02
    assert abs(tech.GC_OSSI_W * tech.GC_OSSI_H / a_sram - 0.35) < 0.02


@given(wz=st.sampled_from([16, 32, 64]), nw=st.sampled_from([32, 64, 128, 256]))
def test_area_monotone_in_capacity(wz, nw):
    r1 = characterize_config(MacroConfig(mem_type="gc_sisi", word_size=wz,
                                         num_words=nw))
    r2 = characterize_config(MacroConfig(mem_type="gc_sisi", word_size=wz,
                                         num_words=nw * 2))
    assert r2["area_um2"] > r1["area_um2"]


def test_macro_area_crossover_above_1kb():
    """Fig 7b: Si-Si macro smaller than SRAM above ~1 Kb; OS-Si smallest."""
    small = {mt: characterize_config(MacroConfig(mem_type=mt, word_size=16,
                                                 num_words=16))["area_um2"]
             for mt in ("sram6t", "gc_sisi", "gc_ossi")}
    big = {mt: characterize_config(MacroConfig(mem_type=mt, word_size=128,
                                               num_words=128))["area_um2"]
           for mt in ("sram6t", "gc_sisi", "gc_ossi")}
    assert small["sram6t"] < small["gc_sisi"]      # dual-port overhead below 1Kb
    assert big["gc_sisi"] < big["sram6t"]          # crossover
    assert big["gc_ossi"] < big["gc_sisi"]         # OS-Si smallest


def test_speed_order_and_leakage():
    """Fig 8: SRAM fastest; GCRAM leakage orders below SRAM."""
    r = {mt: characterize_config(MacroConfig(mem_type=mt, word_size=64,
                                             num_words=64))
         for mt in ("sram6t", "gc_sisi", "gc_ossi")}
    assert r["sram6t"]["f_op_hz"] > r["gc_sisi"]["f_op_hz"] > r["gc_ossi"]["f_op_hz"]
    assert r["gc_sisi"]["p_leak_w"] < 0.2 * r["sram6t"]["p_leak_w"]


def test_wwlls_speeds_up_os_write():
    r0 = characterize_config(MacroConfig(mem_type="gc_ossi", word_size=32,
                                         num_words=64, level_shift=False))
    r1 = characterize_config(MacroConfig(mem_type="gc_ossi", word_size=32,
                                         num_words=64, level_shift=True))
    assert r1["f_write_hz"] > r0["f_write_hz"]
    assert r1["area_um2"] > r0["area_um2"]          # extra ring + LS cells


def test_aspect_ratio_frequency_cliff():
    """Fig 8a: tall 1:1 organizations lose a delay-chain stage vs 4:1."""
    tall = characterize_config(MacroConfig(mem_type="gc_sisi", word_size=32,
                                           num_words=512, mux=1))
    wide = characterize_config(MacroConfig(mem_type="gc_sisi", word_size=128,
                                           num_words=128, mux=1))
    assert wide["f_read_hz"] >= tall["f_read_hz"]
    assert tall["rows"] > wide["rows"]


# -------------------------------------------------------------------------- DSE
def test_table2_reproduced_exactly():
    table = DesignTable.from_configs(design_space())
    for t in gainsight.TASKS:
        l1 = select.select_level(table.metrics, table.families, t.l1).label
        l2 = select.select_level(table.metrics, table.families, t.l2).label
        exp = gainsight.TABLE2_EXPECTED[t.task_id]
        assert l1 == exp["L1"], f"task {t.task_id} L1 {l1} != {exp['L1']}"
        assert l2 == exp["L2"], f"task {t.task_id} L2 {l2} != {exp['L2']}"


def test_feasibility_antitone_in_requirements():
    res = DesignTable.from_configs(design_space()).metrics
    easy = select.feasible_mask(res, 0.2e9, 1e-6)
    hard = select.feasible_mask(res, 2.0e9, 1e-3)
    assert easy.sum() >= hard.sum()
    assert np.all(easy | ~hard)                     # hard ⊆ easy


@given(st.integers(0, 10**6))
def test_pareto_front_correct(seed):
    rng = np.random.default_rng(seed)
    pts = rng.random((40, 3))
    mask = select.pareto_mask(pts)
    for i in range(len(pts)):
        dominated = any(np.all(pts[j] <= pts[i]) and np.any(pts[j] < pts[i])
                        for j in range(len(pts)) if j != i)
        assert mask[i] == (not dominated)


def test_gradient_sizing_improves_cell_delay():
    out = gradient_size_macro(MacroConfig(mem_type="gc_sisi", word_size=64,
                                          num_words=128))
    assert out["speedup"] > 1.0


# -------------------------------------------------------------------- artifacts
@pytest.mark.parametrize("mt", ["gc_sisi", "gc_ossi", "sram6t"])
def test_compiler_flow_drc_lvs_clean(tmp_path, mt):
    rep = generate_all(MacroConfig(mem_type=mt, word_size=32, num_words=64,
                                   level_shift=(mt != "sram6t")), tmp_path)
    assert rep["drc_clean"], rep["drc_errors"][:5]
    assert rep["lvs_clean"], rep["lvs_errors"][:5]
    files = {p.suffix for p in tmp_path.iterdir()}
    assert {".sp", ".v", ".lib", ".lef", ".json"} <= files


def test_artifact_formats():
    cfg = MacroConfig(mem_type="gc_sisi", word_size=16, num_words=32)
    v = emit_verilog(cfg)
    assert "module gc_sisi_16x32" in v and "endmodule" in v
    lib = emit_lib(cfg)
    assert "library (" in lib and "cell_rise (delay_3x3)" in lib
    lef = emit_lef(cfg)
    assert "MACRO gc_sisi_16x32" in lef and "SIZE" in lef
