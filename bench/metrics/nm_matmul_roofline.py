"""Share of its roofline that the n:m compressed matmul kernel
(kernels/nm_spmm.py) reaches: Σ over the kernel's calls in the traced
window of max(operations / bf16 peak, bytes / HBM bandwidth), over Σ of
their device time.  Each call's operations and bytes come from
bench/costs.nm_matmul_cost and the shapes its op spells out in the trace:
result (B, c), activation planes (m, B, g), kept values (keep, c, g),
index planes (1 at 4-bit, keep at 8-bit).  Which bound applies is noted
beside the result."""
from bench import costs

UNIT = "%"
KERNEL = "nm_matmul"
BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s8": 1}


def read(rec):
    calls = ((rec.get("trace") or {}).get("kernel_calls") or {}).get(KERNEL)
    if not calls:
        return None
    ideal = busy = 0.0
    bounds = set()
    for seconds, shapes in calls:
        (yt, (rows, c)), (xt, (m, _, g)), (wt, (keep, _, _)), \
            (_, (planes, _, _)) = shapes[:4]
        cost = costs.nm_matmul_cost(
            rows, c, m * g, m - keep, m, 4 if planes < keep else 8,
            x_bytes=BYTES[xt], w_bytes=BYTES[wt], y_bytes=BYTES[yt])
        t, bound = costs.roofline_seconds(*cost, rec["peak"])
        ideal += t
        busy += seconds
        bounds.add(bound)
    rec.setdefault("notes", {})[KERNEL + "_roofline"] = \
        "bound: " + "+".join(sorted(bounds))
    return 100.0 * ideal / busy
