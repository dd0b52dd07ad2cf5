from real_time_fraud_detection_system_tpu.utils.timing import (  # noqa: F401
    LatencyTracker,
    date_to_epoch_s,
)
from real_time_fraud_detection_system_tpu.utils.logging import (  # noqa: F401
    get_logger,
)
from real_time_fraud_detection_system_tpu.utils.tracing import (  # noqa: F401
    enable_compilation_cache,
    profile_to,
)
from real_time_fraud_detection_system_tpu.utils.metrics import (  # noqa: F401
    LATENCY_BUCKETS_S,
    FlightRecorder,
    MetricsRegistry,
    MetricsServer,
    active_recorder,
    get_registry,
    run_manifest,
    set_active_recorder,
)
from real_time_fraud_detection_system_tpu.utils.trace import (  # noqa: F401
    Tracer,
    get_tracer,
    summarize_chrome,
)
from real_time_fraud_detection_system_tpu.utils.xla_telemetry import (  # noqa: F401,E501
    DeviceMemoryTelemetry,
    RecompileDetector,
    install_compile_telemetry,
    step_signature,
)
