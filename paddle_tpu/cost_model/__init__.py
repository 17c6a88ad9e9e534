"""Roofline cost model.

Reference capability: python/paddle/cost_model/cost_model.py (op-benchmark
table lookups) + auto_parallel/static/cost/ (comm/comp cost classes used by
the tuner).

TPU-native realization: an analytic roofline — per-op FLOPs and bytes from
shapes, per-generation peak FLOPs / HBM bandwidth / ICI bandwidth — which
is how TPU performance is actually reasoned about (compute-bound vs
bandwidth-bound vs ICI-bound).  Used by distributed.auto_tuner to prune
configs without running them.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass
class DeviceSpec:
    name: str
    peak_flops_bf16: float      # FLOP/s
    hbm_bandwidth: float        # bytes/s
    hbm_bytes: float            # capacity
    ici_bandwidth: float        # bytes/s per link
    device_kinds: tuple = ()    # `jax.Device.device_kind` of this chip


# THE peak table: every MFU, roofline share and planner projection reads
# it.  Rows are keyed by generation for the planner's what-if
# projections; ``device_kinds`` ties a row to what JAX reports for a
# live device, and :func:`device_spec` resolves one through it — an
# accelerator that is in no row is an error, never a default.
# Sources: Google Cloud TPU documentation, "System architecture" page of
# each generation (v5e: 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s,
# 1,600 Gbit/s interconnect per chip over 4 links).  device_kind
# strings: jax/_src/pallas/mosaic/tpu_info.py; "TPU v5 lite" is the
# string the v5e chip reported in PR 21's chip run.
DEVICE_SPECS = {
    "v4": DeviceSpec("v4", 275e12, 1.2e12, 32e9, 50e9, ("TPU v4",)),
    "v5e": DeviceSpec("v5e", 197e12, 819e9, 16e9, 50e9,
                      ("TPU v5 lite", "TPU v5e")),
    "v5p": DeviceSpec("v5p", 459e12, 2.76e12, 95e9, 100e9,
                      ("TPU v5", "TPU v5p")),
    "v6e": DeviceSpec("v6e", 918e12, 1.64e12, 32e9, 100e9,
                      ("TPU v6 lite", "TPU v6e")),
    # a stand-in host for the planner's CPU-mesh tests only: it names no
    # device_kind, so no live device ever resolves to it
    "cpu": DeviceSpec("cpu", 1e12, 0.1e12, 64e9, 10e9),
}


def device_spec(device=None):
    """The :class:`DeviceSpec` of a live JAX device (default
    ``jax.devices()[0]``), found by its ``device_kind``.  ``None`` for a
    CPU — a host has no peak to hold a device metric against, so nothing
    computes an MFU there.  An accelerator the table does not list
    raises."""
    import jax
    d = device if device is not None else jax.devices()[0]
    if d.platform == "cpu":
        return None
    for spec in DEVICE_SPECS.values():
        if d.device_kind in spec.device_kinds:
            return spec
    raise KeyError(
        f"no peak-performance entry for device_kind {d.device_kind!r} "
        f"(platform {d.platform!r}): add it to cost_model.DEVICE_SPECS "
        "with its source")


def device_peak_flops(device=None):
    """Peak bf16 FLOP/s of one live device for MFU accounting, from
    :data:`DEVICE_SPECS`; ``None`` on a CPU, ``KeyError`` for an
    unknown accelerator."""
    spec = device_spec(device)
    return None if spec is None else spec.peak_flops_bf16


def matmul_cost(m, k, n, dtype_bytes=2, device="v5e"):
    """Returns (seconds, bound) for an m×k @ k×n matmul."""
    spec = DEVICE_SPECS[device]
    flops = 2.0 * m * k * n
    bytes_moved = dtype_bytes * (m * k + k * n + m * n)
    t_compute = flops / spec.peak_flops_bf16
    t_memory = bytes_moved / spec.hbm_bandwidth
    return max(t_compute, t_memory), \
        "compute" if t_compute >= t_memory else "memory"


def collective_cost(bytes_total, n_devices, kind="all_reduce",
                    device="v5e"):
    """Ring-algorithm time on ICI (reference analog: auto_parallel
    comm-cost classes)."""
    spec = DEVICE_SPECS[device]
    if n_devices <= 1:
        return 0.0
    factor = {"all_reduce": 2.0 * (n_devices - 1) / n_devices,
              "all_gather": (n_devices - 1) / n_devices,
              "reduce_scatter": (n_devices - 1) / n_devices,
              "all_to_all": (n_devices - 1) / n_devices,
              "p2p": 1.0}[kind]
    return bytes_total * factor / spec.ici_bandwidth


@dataclass
class TransformerCost:
    """Per-step cost estimate for a GPT-style model under a hybrid config.

    ``t_compute`` (math + the HBM-bound optimizer update) and ``t_comm``
    (per-axis collectives) are the components the auto-layout planner
    recombines when a measured COMM_BUDGET replaces the analytic comm
    term (``planner.py``)."""
    step_time_s: float
    mfu: float
    hbm_per_device: float
    bound: str
    t_compute: float = 0.0
    t_comm: float = 0.0


def transformer_step_cost(n_params, n_layers, hidden, batch, seq,
                          dp=1, mp=1, pp=1, sharding=1, device="v5e",
                          dtype_bytes=2, grad_accum=1, recompute=False):
    """Roofline step-time for one training step (fwd+bwd ≈ 6·P·T flops).

    recompute=True models layer-boundary activation checkpointing: one
    stored activation per layer instead of ~8, at the cost of an extra
    forward in the backward pass (flops ×4/3)."""
    spec = DEVICE_SPECS[device]
    tokens = batch * seq
    flops = 6.0 * n_params * tokens
    if recompute:
        flops *= 4.0 / 3.0
    # fp32 (dtype_bytes=4) runs the MXU at ~half its bf16 rate
    peak = spec.peak_flops_bf16 * (0.5 if dtype_bytes >= 4 else 1.0)
    n_dev = dp * mp * pp * sharding
    t_compute = flops / (peak * n_dev)
    # 1F1B pipeline bubble: with m micro-batches the schedule spans
    # (m + pp - 1) slots of which m do useful work per stage
    # (reference: auto_parallel/static/tuner/parallel_tuner.py pp cost)
    if pp > 1:
        m = max(int(grad_accum), 1)
        t_compute *= (m + pp - 1) / m

    # memory per device: params+grads+opt (ZeRO over sharding·dp), acts
    state_bytes = n_params * (dtype_bytes + dtype_bytes + 8)
    state_per_dev = state_bytes / (mp * pp * max(sharding, 1))
    act_factor = 1 if recompute else 8
    act_bytes = (dtype_bytes * batch * seq * hidden * n_layers
                 * act_factor / (dp * mp * pp * grad_accum))
    hbm = state_per_dev + act_bytes

    # optimizer update: the fused Adam step streams params, grads and
    # both moments (read + write ≈ 32 B/param fp32) once per step —
    # HBM-bound work REPLICATED across dp, divided only by the axes
    # that shard the state (mp/pp/ZeRO).  This is what makes pure-dp
    # lose to dp×mp on parameter-heavy models even at equal FLOPs.
    t_update = (32.0 * n_params / (mp * pp * max(sharding, 1))
                / spec.hbm_bandwidth)
    t_comp = t_compute + t_update

    # comms: dp grad all-reduce + mp per-layer collectives
    grad_bytes = dtype_bytes * n_params / (mp * pp)
    t_dp = collective_cost(grad_bytes, dp * sharding, "all_reduce", device)
    act_per_layer = dtype_bytes * batch * seq * hidden / dp
    t_mp = (collective_cost(act_per_layer, mp, "all_reduce", device)
            * 4 * n_layers / pp)
    t_pp = collective_cost(act_per_layer, 2, "p2p", device) * 2 * (pp - 1)
    t_comm = t_dp + t_mp + t_pp

    step = max(t_comp, t_comm) + 0.1 * min(t_comp, t_dp + t_mp)
    mfu = flops / (step * peak * n_dev)
    bound = "compute" if t_comp >= t_comm else "comm"
    return TransformerCost(step, mfu, hbm, bound, t_comp, t_comm)


class CostModel:
    """reference: cost_model.py CostModel — profile-or-estimate interface."""

    def __init__(self, device="v5e"):
        self.device = device

    def get_static_op_time(self, op_name, forward=True, dtype="float32"):
        raise NotImplementedError(
            "per-op benchmark tables are CI-side in the reference; use the "
            "analytic entries (matmul_cost/collective_cost) instead")

    def estimate_step(self, **kwargs):
        return transformer_step_cost(device=self.device, **kwargs)


from .planner import (  # noqa: E402  (planner needs the roofline above)
    BudgetSchemaError, COMM_BUDGET_SCHEMA_VERSION, LayoutPlan,
    load_comm_budgets, plan_layout, project_comm_seconds, validate_budget,
)
