"""Step program: the FLOPs one step requires (the family's own count, no
recomputed operations) over device_step_ms x peak x chips. A device-time
utilisation: the end-to-end utilisation is this times (1 - idle share)."""


def read(record):
    traced, peaks = record.get("traced"), record.get("peaks")
    if not traced or not peaks or not traced["step_busy_ms"]:
        return None
    least = record["train_flops"] / (peaks["bf16_flops_per_s"]
                                     * record["chips"])
    return 100.0 * least / (traced["step_busy_ms"] / 1e3)
