from .checkpoint import (
    SERVE_CONFIG_KEYS,
    CheckpointIOError,
    CheckpointManager,
    ConfigDriftError,
    check_resume_config,
    check_serve_config,
    load_run_config,
    save_run_config,
)
from .compile_cache import default_cache_dir, enable_compilation_cache
from .logging import MetricLogger
from .viz import save_density_visualization
from .profiling import (
    StepTimer,
    await_devices,
    bench_device,
    device_watchdog,
    emit_null_result,
    pallas_interpret,
    profile_trace,
    requested_platform,
)

__all__ = [
    "CheckpointIOError",
    "CheckpointManager",
    "ConfigDriftError",
    "SERVE_CONFIG_KEYS",
    "check_resume_config",
    "check_serve_config",
    "load_run_config",
    "save_run_config",
    "MetricLogger",
    "save_density_visualization",
    "StepTimer",
    "profile_trace",
    "enable_compilation_cache",
    "default_cache_dir",
    "await_devices",
    "bench_device",
    "device_watchdog",
    "emit_null_result",
    "pallas_interpret",
    "requested_platform",
]
