"""FROST core: energy accounting (Eqs 1-5), ED^mP, the F(x) fit (Eqs 6-7),
the downhill simplex, the cap profiler, policies, and power shifting."""
import numpy as np
import pytest

from repro.core import (BALANCED, ENERGY_LEAN, LATENCY_LEAN, CapProfiler,
                        ClusterNode, EnergyLedger, PowerCappedDevice,
                        PowerSample, QoSPolicy, RTX_3080, RTX_3090, TPU_V5E,
                        WorkloadProfile, allocate_power, detect_stragglers,
                        device_spec, dram_power_estimate, edp, f_curve,
                        fit_cost_curve, integrate_power, minimize_fit,
                        nelder_mead)
from repro.core.edp import CapMeasurement, normalized_costs
from repro.core.simplex import minimize_scalar_on_interval


class _Dev:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


@pytest.mark.parametrize("platform,kind,want", [
    ("tpu", "TPU v5 lite", TPU_V5E),     # jax's device_kind for a v5e
    ("cpu", "cpu", TPU_V5E),             # CPU stands in for the target
    ("tpu", "TPU v9 mystery", None),     # unknown chip: an error
])
def test_device_spec_by_device_kind(platform, kind, want):
    if want is None:
        with pytest.raises(ValueError, match="TPU v9 mystery"):
            device_spec(_Dev(platform, kind))
    else:
        assert device_spec(_Dev(platform, kind)) is want


# --------------------------------------------------------------------------
# energy accounting
# --------------------------------------------------------------------------
def test_dram_rule_of_thumb():
    # paper setup no.1: 4 x 16 GB DIMMs -> 4 * 3/8 * 16 = 24 W
    assert dram_power_estimate(4, 16) == pytest.approx(24.0)
    # setup no.2: 4 x 32 GB -> 48 W
    assert dram_power_estimate(4, 32) == pytest.approx(48.0)


def test_integrate_power_trapezoid():
    samples = [PowerSample(t=float(t), cpu_w=100.0) for t in range(11)]
    assert integrate_power(samples) == pytest.approx(1000.0)


def test_energy_ledger_idle_subtraction():
    ledger = EnergyLedger(
        idle_trace=[PowerSample(t=float(t), cpu_w=50.0) for t in range(5)])
    ledger.extend([PowerSample(t=float(t), cpu_w=150.0) for t in range(11)])
    rep = ledger.report()
    assert rep.gross_j == pytest.approx(1500.0)
    assert rep.idle_j == pytest.approx(500.0)     # 50 W x 10 s
    assert rep.net_j == pytest.approx(1000.0)
    assert rep.mean_power_w == pytest.approx(150.0)


def test_profile_energy_enters_report():
    ledger = EnergyLedger()
    ledger.add_profile_energy(800.0)              # Eq 4 leading term
    ledger.extend([PowerSample(t=0.0, gpu_w=100.0),
                   PowerSample(t=1.0, gpu_w=100.0)])
    assert ledger.report().net_j == pytest.approx(900.0)


# --------------------------------------------------------------------------
# ED^mP
# --------------------------------------------------------------------------
def test_edp_exponent_semantics():
    assert edp(10, 2, 1) == 20
    assert edp(10, 2, 2) == 40
    assert edp(10, 2, 3) == 80
    with pytest.raises(ValueError):
        edp(-1, 1)


def test_higher_exponent_prefers_faster_configs():
    fast = CapMeasurement(cap=1.0, energy_j=100.0, delay_s=1.0, samples=10)
    slow = CapMeasurement(cap=0.5, energy_j=40.0, delay_s=2.0, samples=10)
    # energy-lean: slow/capped wins; latency-lean: fast wins
    assert slow.cost(1) < fast.cost(1)
    assert slow.cost(3) > fast.cost(3)


# --------------------------------------------------------------------------
# simplex + fit
# --------------------------------------------------------------------------
def test_nelder_mead_rosenbrock():
    f = lambda x: (1 - x[0]) ** 2 + 100 * (x[1] - x[0] ** 2) ** 2
    res = nelder_mead(f, [-1.2, 1.0], max_iter=5000)
    np.testing.assert_allclose(res.x, [1.0, 1.0], atol=1e-4)


def test_minimize_scalar_on_interval():
    x, fx = minimize_scalar_on_interval(lambda x: (x - 0.42) ** 2, 0.3, 1.0)
    assert x == pytest.approx(0.42, abs=1e-5)


def test_fit_recovers_convex_cost_curve():
    caps = np.arange(0.3, 1.01, 0.1)
    true = 0.4 * np.exp(-6 * (caps - 0.3)) + 0.8 / (1 + np.exp(-8 * (caps - 0.7))) + 0.6
    fit = fit_cost_curve(caps, true)
    assert fit.accepted, f"rel_rmse={fit.rel_rmse}"
    x_opt, _ = minimize_fit(fit)
    brute = caps[np.argmin(true)]
    dense = np.linspace(0.3, 1.0, 1000)
    brute_dense = dense[np.argmin(fit(dense))]
    assert abs(x_opt - brute_dense) < 0.02
    assert abs(x_opt - brute) <= 0.15


def test_fit_rejects_garbage_and_falls_back():
    rng = np.random.default_rng(0)
    caps = np.arange(0.3, 1.01, 0.1)
    y = rng.uniform(0.0, 5.0, size=caps.size)      # unfittable noise
    fit = fit_cost_curve(caps, y)
    x_opt, v = minimize_fit(fit)
    if not fit.accepted:
        # falls back to the best *measured* probe — never extrapolates
        assert x_opt == pytest.approx(caps[np.argmin(y)])


# --------------------------------------------------------------------------
# the analytic device + profiler (paper phenomenology must EMERGE)
# --------------------------------------------------------------------------
def _compute_bound_wl():
    return WorkloadProfile(name="big", flops_per_step=5e12,
                           hbm_bytes_per_step=2e9, samples_per_step=128)


def _memory_bound_wl():
    return WorkloadProfile(name="decode", flops_per_step=5e10,
                           hbm_bytes_per_step=1.5e10, samples_per_step=128)


def test_capping_stretches_compute_bound_steps():
    dev = PowerCappedDevice(TPU_V5E)
    wl = _compute_bound_wl()
    t100 = dev.estimate(wl, 1.0).step_time_s
    t40 = dev.estimate(wl, 0.4).step_time_s
    assert t40 > 1.15 * t100          # compute-bound: deep caps hurt


def test_capping_nearly_free_when_memory_bound():
    dev = PowerCappedDevice(TPU_V5E)
    wl = _memory_bound_wl()
    t100 = dev.estimate(wl, 1.0).step_time_s
    t40 = dev.estimate(wl, 0.4).step_time_s
    assert t40 < 1.10 * t100          # paper Sec IV-C observation
    e100 = dev.estimate(wl, 1.0).energy_j
    e40 = dev.estimate(wl, 0.4).energy_j
    assert e40 < e100                 # and saves energy


def test_profiler_selects_deeper_cap_for_memory_bound():
    class W:
        def __init__(self, wl):
            self.dev = PowerCappedDevice(RTX_3080)
            self.wl = wl

        def probe(self, cap, duration_s):
            return self.dev.probe(self.wl, cap, duration_s)

    d_mem = CapProfiler(W(_memory_bound_wl()), policy=BALANCED).run()
    d_cmp = CapProfiler(W(_compute_bound_wl()), policy=LATENCY_LEAN).run()
    assert d_mem.cap <= d_cmp.cap
    assert 0.3 <= d_mem.cap <= 1.0
    assert d_mem.predicted_energy_saving > 0.0


def test_profiler_respects_policy_window_and_delay_bound():
    class W:
        dev = PowerCappedDevice(RTX_3090)

        def probe(self, cap, duration_s):
            return self.dev.probe(_compute_bound_wl(), cap, duration_s)

    pol = QoSPolicy(policy_id="tight", edp_exponent=1.0,
                    max_delay_increase=0.02)
    d = CapProfiler(W(), policy=pol).run()
    assert d.predicted_delay_increase <= 0.02 + 1e-6


def test_edp_exponent_monotone_in_cap():
    """Paper Fig 5: more delay weight -> higher optimal cap."""
    class W:
        dev = PowerCappedDevice(RTX_3080)

        def probe(self, cap, duration_s):
            return self.dev.probe(_compute_bound_wl(), cap, duration_s)

    caps = [CapProfiler(W(), policy=QoSPolicy(edp_exponent=m)).run().cap
            for m in (1.0, 2.0, 3.0)]
    assert caps[0] <= caps[1] <= caps[2] + 1e-9


# --------------------------------------------------------------------------
# power shifting / stragglers
# --------------------------------------------------------------------------
def test_detect_stragglers():
    out = detect_stragglers([1.0, 1.02, 1.5, 0.98], threshold=1.15)
    assert out == [2]


def test_power_shift_equalises_step_time():
    wl = _compute_bound_wl()
    healthy = ClusterNode("n0", PowerCappedDevice(TPU_V5E), wl)
    derated = ClusterNode("n1", PowerCappedDevice(TPU_V5E, derate=0.8), wl)
    plan = allocate_power([healthy, derated], 2 * 0.9 * TPU_V5E.tdp_w)
    assert plan.feasible
    caps = {a.node_id: a.cap for a in plan.allocations}
    # the derated node gets MORE power budget than the healthy one
    assert caps["n1"] >= caps["n0"]
    times = [a.step_time_s for a in plan.allocations]
    assert max(times) / min(times) < 1.2
    total = sum(a.power_w for a in plan.allocations)
    assert total <= plan.global_budget_w * 1.001
