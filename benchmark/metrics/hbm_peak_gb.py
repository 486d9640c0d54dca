"""The most the fullest chip held while the window's rounds ran, as the
program reads it after each round's barrier (``RoundRecord.device_memory``):
the allocator's buffers at their peak plus the scratch the runtime reserves
while the round program is loaded, ``peak_bytes_in_use + bytes_reserved``,
the largest over the window's rounds, in GB (1e9 bytes). The harness's own
``device.memory_peak_bytes`` is the same sum read once after the window.
Silent on a program without ``device_memory`` and on a backend that reports
none."""


def read(run):
    held = [getattr(r, "device_memory", None) for r in run["records"]]
    if not held or not all(held):
        return None
    return max(m["peak_bytes_in_use"] + m["bytes_reserved"] for m in held) / 1e9
