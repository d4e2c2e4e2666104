"""Operations the prune job needs (bench/costs.prune_block_ops: both
calibration forwards, the Hessians, the Thanos n:m recurrence) over the
window's wall time and the chip's bf16 peak."""
UNIT = "%"


def read(rec):
    calls = rec["calls"]
    return 100.0 * rec["job_ops"] * len(calls) / (
        sum(c["wall_s"] for c in calls) * rec["peak"]["bf16_flops"])
