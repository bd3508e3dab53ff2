from lightctr_tpu.utils.profiling import annotate, trace
from lightctr_tpu.utils.system import host_memory_usage, device_memory_stats

__all__ = [
    "annotate",
    "trace",
    "host_memory_usage",
    "device_memory_stats",
]
