"""DVFS operating points through the device + power model (ISSUE 8).

Pins the tentpole's device-layer contracts: the paper's 300 MHz / 0.8 V
point is the *calibration anchor* (scales are exactly 1.0 there, so every
historical number is bit-identical), dynamic power scales with f*V^2,
leakage with voltage, both monotonically; op points key the memoization
layers; and ``fuse_breakdowns`` normalizes mixed-op-point chains per
stage.
"""

import dataclasses

import pytest

from repro.core import (EGPU_4T, EGPU_8T, EGPU_16T, OP_ANCHOR,
                        OPERATING_POINTS, OperatingPoint)
from repro.core.machine import PhaseBreakdown, fuse_breakdowns
from repro.core.power import (characterize, dynamic_scale, egpu_active_power_mw,
                              egpu_energy_j, egpu_idle_power_mw, leakage_scale)

LOW = OPERATING_POINTS["low"]
TURBO = OPERATING_POINTS["turbo"]


# ---------------------------------------------------------------------------
# OperatingPoint / EGPUConfig.at
# ---------------------------------------------------------------------------
def test_operating_point_table_and_anchor():
    assert OP_ANCHOR.freq_hz == 300e6 and OP_ANCHOR.voltage_v == 0.8
    assert OPERATING_POINTS["nominal"] is OP_ANCHOR
    assert LOW.freq_hz < OP_ANCHOR.freq_hz < TURBO.freq_hz
    assert LOW.voltage_v < OP_ANCHOR.voltage_v < TURBO.voltage_v


@pytest.mark.parametrize("freq,volt", [(0.0, 0.8), (-1.0, 0.8),
                                       (300e6, 0.0), (300e6, -0.5)])
def test_operating_point_rejects_nonpositive(freq, volt):
    with pytest.raises(ValueError):
        OperatingPoint("bad", freq, volt).validate()


def test_config_at_rebases_and_validates():
    c = EGPU_16T.at(TURBO)
    assert (c.freq_hz, c.voltage_v) == (TURBO.freq_hz, TURBO.voltage_v)
    assert c.total_threads == EGPU_16T.total_threads  # only DVFS moved
    assert c.operating_point is TURBO
    assert EGPU_16T.operating_point is OP_ANCHOR
    assert dataclasses.replace(EGPU_16T, voltage_v=0.71) \
        .operating_point.name == "custom"
    with pytest.raises(ValueError):
        dataclasses.replace(EGPU_16T, voltage_v=-1.0).validate()


# ---------------------------------------------------------------------------
# scales: exact anchor identity, monotonicity
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("config", [EGPU_4T, EGPU_8T, EGPU_16T])
def test_scales_are_exactly_one_at_anchor(config):
    assert dynamic_scale(config) == 1.0
    assert leakage_scale(config) == 1.0
    assert dynamic_scale(config.at(OP_ANCHOR)) == 1.0


@pytest.mark.parametrize("config", [EGPU_4T, EGPU_8T, EGPU_16T])
def test_anchor_bit_identity(config):
    """Rebasing onto the anchor is a no-op bit for bit: characterize,
    active power, idle power and energy all reproduce the calibrated
    numbers exactly (not approximately)."""
    at = config.at(OP_ANCHOR)
    assert characterize(at) == characterize(config)
    assert egpu_active_power_mw(at) == egpu_active_power_mw(config)
    assert egpu_idle_power_mw(at) == egpu_idle_power_mw(config)
    pb = PhaseBreakdown(startup=1000, scheduling=500, transfer=2000,
                        compute=30000, freq_hz=config.freq_hz)
    assert egpu_energy_j(at, pb) == egpu_energy_j(config, pb)


def test_power_monotone_in_frequency_and_voltage():
    for base in (EGPU_8T, EGPU_16T):
        p_low = egpu_active_power_mw(base.at(LOW))
        p_nom = egpu_active_power_mw(base)
        p_turbo = egpu_active_power_mw(base.at(TURBO))
        assert p_low < p_nom < p_turbo
        # frequency alone (V fixed): dynamic power is linear in f
        faster = dataclasses.replace(base, freq_hz=base.freq_hz * 2)
        assert egpu_active_power_mw(faster) > p_nom
        # voltage alone (f fixed): both dynamic AND leakage rise
        hotter = dataclasses.replace(base, voltage_v=base.voltage_v * 1.1)
        assert egpu_active_power_mw(hotter) > p_nom
        assert characterize(hotter).total_leak_uw \
            > characterize(base).total_leak_uw
        assert egpu_idle_power_mw(base.at(LOW)) \
            < egpu_idle_power_mw(base) < egpu_idle_power_mw(base.at(TURBO))


def test_low_point_is_more_efficient_per_request():
    """The DVFS trade the serving bench exploits: low is slower but
    cheaper per unit of work; turbo faster but costlier."""
    pb = PhaseBreakdown(startup=1000, scheduling=500, transfer=2000,
                        compute=30000, freq_hz=EGPU_16T.freq_hz)

    def energy_at(point):
        c = EGPU_16T.at(point)
        return egpu_energy_j(c, dataclasses.replace(pb, freq_hz=c.freq_hz))

    assert energy_at(LOW) < energy_at(OP_ANCHOR) < energy_at(TURBO)


# ---------------------------------------------------------------------------
# op points key the memo layers
# ---------------------------------------------------------------------------
def test_graph_cache_keys_include_op_point():
    import jax.numpy as jnp
    import numpy as np

    from repro.core import APU, Kernel, Stage
    from repro.serve import GraphCache

    k = Kernel("scale", executor=lambda x: (x * 2.0,))
    stages = [Stage(k, n_inputs=1)]
    x = jnp.asarray(np.ones((4, 4), np.float32))
    cache = GraphCache(capacity=8)
    APU(EGPU_16T, graph_cache=cache).offload(stages, (x,))
    APU(EGPU_16T, graph_cache=cache).offload(stages, (x,))
    assert (cache.hits, cache.misses) == (1, 1)       # same config: shared
    APU(EGPU_16T.at(LOW), graph_cache=cache).offload(stages, (x,))
    assert (cache.hits, cache.misses) == (1, 2)       # op point: new entry


# ---------------------------------------------------------------------------
# fuse_breakdowns across op points (satellite b)
# ---------------------------------------------------------------------------
def test_fuse_chain_mixed_op_points_normalizes_per_stage():
    """Regression: chain-mode fusion used to reject mixed clocks outright;
    now each stage's cycles are normalized by ITS OWN op-point frequency
    onto the fastest clock, in both chain and DAG mode."""
    a = PhaseBreakdown(startup=300, scheduling=150, transfer=900,
                       compute=3000, freq_hz=EGPU_16T.at(TURBO).freq_hz)
    b = PhaseBreakdown(startup=300, scheduling=150, transfer=900,
                       compute=3000, freq_hz=EGPU_16T.at(LOW).freq_hz)
    chain = fuse_breakdowns([a, b])
    dag = fuse_breakdowns([a, b], deps=[(), (0,)])
    assert chain.freq_hz == TURBO.freq_hz
    assert chain == dag                                # same serial shape
    # wall-clock truth is preserved: each stage contributes its own
    # seconds, overheads paid once at the max normalized cost
    expect_s = (a.transfer + a.compute) / a.freq_hz \
        + (b.transfer + b.compute) / b.freq_hz \
        + max((a.startup + a.scheduling) / a.freq_hz,
              (b.startup + b.scheduling) / b.freq_hz)
    assert chain.total_s == pytest.approx(expect_s, rel=1e-12)
    # uniform chains stay bit-identical (scale factor is exactly 1.0)
    uniform = fuse_breakdowns([a, dataclasses.replace(a)])
    assert uniform.freq_hz == a.freq_hz
    assert uniform.transfer == a.transfer * 2


def test_characterize_lru_does_not_alias_op_points():
    seen = {characterize(EGPU_16T).total_leak_uw,
            characterize(EGPU_16T.at(LOW)).total_leak_uw,
            characterize(EGPU_16T.at(TURBO)).total_leak_uw}
    assert len(seen) == 3
