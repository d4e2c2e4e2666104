"""Operations the model needs for every token the traced span produced
(bench/costs: each admission's prompt, each decode token at its position,
linears at the n:m kept share, attention over the live context), over the
span's host-clock length and the chip's bf16 peak."""
UNIT = "%"


def read(rec):
    if not rec.get("traced_ops"):
        return None
    return 100.0 * rec["traced_ops"] / (rec["traced_host_s"]
                                        * rec["peak"]["bf16_flops"])
