"""``moe_gmm_roofline``: the least time the chip could take for a step's
grouped products (the larger of needed operations over the bf16 peak and
needed bytes over the HBM peak, from ``chipbench/flops_lfm2.py``) over the
kernels' measured time, in percent (layer: kernels).  At 2048 x 1792 and
some thousands of rows a group the compute bound applies.

The need is counted at the EXPECTED load (tokens x top_k x held / routed
pairs a step).  The traffic is skewed and every seed draws other weights,
so a run whose router sends more pairs to the held experts than that does
more work than is counted and reads LOW, one that sends fewer reads HIGH by
the same factor (``held_share`` / 0.25, from the step's counters): compare
the number across PRs on one seed, and hold a reading near 100 against the
run's ``held_share`` before believing it."""

from chipbench import flops, flops_lfm2
from chipbench.layer_metrics import moe_gmm_ms


def read(events, host, context):
    sizes = context["sizes"]
    if context["peaks"] is None or not events["devices"]:
        return None
    if sizes.get("moe_matmul_impl") != "pallas":
        return None
    measured_s = moe_gmm_ms.gmm_ns_per_step(events, host) / 1e9
    if measured_s <= 0:
        return None
    flop, nbytes = flops_lfm2.moe_gmm_train_flop_and_bytes(sizes)
    least_s, _ = flops.roofline_seconds(flop, nbytes, context["peaks"])
    return 100.0 * least_s / measured_s
