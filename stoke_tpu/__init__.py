"""stoke_tpu: a TPU-native declarative training framework.

Brand-new JAX/XLA/pjit implementation of the capabilities of the reference
``stoke`` library (facade + status validation + one SPMD engine replacing the
DDP/Horovod/DeepSpeed/fairscale/AMP backend zoo).  Public API surface mirrors
the reference ``__all__`` (stoke/__init__.py:17-43) adapted to TPU concepts.
"""

from stoke_tpu.configs import (
    ActivationCheckpointingConfig,
    AttributionConfig,
    CheckpointConfig,
    CheckpointFormat,
    ClipGradConfig,
    ClipGradNormConfig,
    CommConfig,
    CompileConfig,
    DataParallelConfig,
    DeviceOptions,
    DistributedInitConfig,
    DistributedOptions,
    FleetConfig,
    FSDPConfig,
    HealthConfig,
    LossReduction,
    MemoryConfig,
    MeshConfig,
    NumericsConfig,
    OffloadDiskConfig,
    OffloadOptimizerConfig,
    OffloadParamsConfig,
    OpsPlaneConfig,
    OSSConfig,
    ParamNormalize,
    PartitionRulesConfig,
    PrecisionConfig,
    PrecisionOptions,
    ProfilerConfig,
    ResilienceConfig,
    SDDPConfig,
    ServeConfig,
    TelemetryConfig,
    TensorboardConfig,
    TraceConfig,
    ShardingOptions,
    StokeOptimizer,
)
from stoke_tpu.serving.sampling import SamplingParams
from stoke_tpu.serving.slo import RequestSLO
from stoke_tpu.data import (
    ArrayDataset,
    BucketedDistributedSampler,
    RaggedSequenceDataset,
    StokeDataLoader,
)
from stoke_tpu.engine import (
    DeferredOutput,
    FlaxModelAdapter,
    FunctionalModelAdapter,
    ModelAdapter,
)
from stoke_tpu.facade import Stoke
from stoke_tpu.resilience import PreemptedError
from stoke_tpu.status import StokeStatus, StokeValidationError
from stoke_tpu.telemetry.health import HealthHaltError
from stoke_tpu.utils import init_module

__version__ = "0.1.0"

__all__ = [
    "Stoke",
    "StokeStatus",
    "StokeValidationError",
    "HealthHaltError",
    "PreemptedError",
    "init_module",
    "StokeOptimizer",
    "StokeDataLoader",
    "BucketedDistributedSampler",
    "ArrayDataset",
    "RaggedSequenceDataset",
    # enums
    "DeviceOptions",
    "DistributedOptions",
    "PrecisionOptions",
    "ShardingOptions",
    "ParamNormalize",
    "LossReduction",
    "CheckpointFormat",
    # configs
    "AttributionConfig",
    "PrecisionConfig",
    "ClipGradConfig",
    "ClipGradNormConfig",
    "CommConfig",
    "CompileConfig",
    "DataParallelConfig",
    "MeshConfig",
    "DistributedInitConfig",
    "OSSConfig",
    "SDDPConfig",
    "FleetConfig",
    "FSDPConfig",
    "HealthConfig",
    "MemoryConfig",
    "NumericsConfig",
    "OffloadDiskConfig",
    "OffloadOptimizerConfig",
    "OffloadParamsConfig",
    "OpsPlaneConfig",
    "PartitionRulesConfig",
    "ActivationCheckpointingConfig",
    "CheckpointConfig",
    "ProfilerConfig",
    "ResilienceConfig",
    "ServeConfig",
    "SamplingParams",
    "RequestSLO",
    "TelemetryConfig",
    "TensorboardConfig",
    "TraceConfig",
    # adapters
    "ModelAdapter",
    "FlaxModelAdapter",
    "FunctionalModelAdapter",
    "DeferredOutput",
]
