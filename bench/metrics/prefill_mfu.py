"""Required prefill FLOPs over the chip's bf16 peak times the prefill
program's device time (device trace), in %."""


def read(rec):
    tr = rec["trace"]
    mods = tr and tr["module_s"]["prefill"]
    if not mods or not rec["peaks"]:
        return None
    t = sum(mods) / len(mods)
    return 100.0 * rec["work"]["prefill"]["flops"] / (
        rec["peaks"]["bf16_flops_per_s"] * t)
