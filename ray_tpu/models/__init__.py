"""ray_tpu.models — flagship JAX model families.

The reference ships no model code (models are torch user code fed to
TorchTrainer); here model families are first-class so the train/serve/rllib
libraries and benchmarks have TPU-native flagships. Llama-2 is the
north-star benchmark model (BASELINE.md: ≥40% MFU on v5e).
"""

from ray_tpu.models.llama import (
    LlamaConfig,
    llama_init,
    llama_forward,
    llama_hidden,
    llama_loss,
    llama_param_specs,
)
from ray_tpu.models.mlp import MLPConfig, mlp_init, mlp_forward
from ray_tpu.models.vit import (
    ViTConfig,
    vit_init,
    vit_forward,
    vit_loss,
    vit_param_specs,
)
from ray_tpu.models.hybrid import HybridConfig, hybrid_init
from ray_tpu.models.mla import MlaConfig, mla_init
from ray_tpu.models.gdn import GdnConfig, gdn_init
from ray_tpu.models.moe import (
    MoeConfig,
    moe_init,
    moe_ffn_dropless,
    moe_forward,
    moe_loss,
    moe_param_specs,
)
from ray_tpu.models.lora import (
    LoraConfig,
    lora_init,
    lora_merge,
    lora_num_params,
    lora_param_specs,
    lora_stack_specs,
    make_lora_train_step,
)
from ray_tpu.models.adapter_pool import AdapterPool
from ray_tpu.models.t5 import (
    T5Config,
    t5_init,
    t5_forward,
    t5_encode,
    t5_decode,
    t5_loss,
    t5_param_specs,
)
from ray_tpu.models.engine import DecodeEngine
from ray_tpu.models.engine_metrics import EngineMetrics
from ray_tpu.models.engine_trace import EngineTracer, NullEngineTracer
from ray_tpu.models.fault_injection import FaultInjector, InjectedFault
from ray_tpu.models.fleet import (
    EngineStatsAutoscaler,
    FleetAutoscalingConfig,
    FleetError,
    FleetHealthConfig,
    FleetRouter,
    LLMFleet,
    PowerOfTwoAffinityRouter,
    ReplicaUnavailable,
    RetriesExhausted,
    RoundRobinRouter,
)
from ray_tpu.models.prefix_cache import PrefixCacheIndex
from ray_tpu.models.scheduler import (
    AdapterAffinityPolicy,
    EngineDraining,
    EngineOverloaded,
    FIFOPolicy,
    PrefixAffinityPolicy,
    PriorityPolicy,
    SchedulerPolicy,
    SubmitTimeout,
)

__all__ = [
    "LlamaConfig",
    "llama_init",
    "llama_forward",
    "llama_hidden",
    "llama_loss",
    "llama_param_specs",
    "ViTConfig",
    "vit_init",
    "vit_forward",
    "vit_loss",
    "vit_param_specs",
    "MLPConfig",
    "mlp_init",
    "mlp_forward",
    "HybridConfig",
    "hybrid_init",
    "MlaConfig",
    "mla_init",
    "GdnConfig",
    "gdn_init",
    "MoeConfig",
    "moe_init",
    "moe_ffn_dropless",
    "moe_forward",
    "moe_loss",
    "moe_param_specs",
    "LoraConfig",
    "lora_init",
    "lora_merge",
    "lora_num_params",
    "lora_param_specs",
    "lora_stack_specs",
    "make_lora_train_step",
    "AdapterPool",
    "AdapterAffinityPolicy",
    "T5Config",
    "t5_init",
    "t5_forward",
    "t5_encode",
    "t5_decode",
    "t5_loss",
    "t5_param_specs",
    "DecodeEngine",
    "EngineDraining",
    "EngineMetrics",
    "EngineOverloaded",
    "EngineTracer",
    "NullEngineTracer",
    "EngineStatsAutoscaler",
    "FaultInjector",
    "FIFOPolicy",
    "FleetAutoscalingConfig",
    "FleetError",
    "FleetHealthConfig",
    "FleetRouter",
    "InjectedFault",
    "LLMFleet",
    "PowerOfTwoAffinityRouter",
    "PrefixAffinityPolicy",
    "PrefixCacheIndex",
    "PriorityPolicy",
    "ReplicaUnavailable",
    "RetriesExhausted",
    "RoundRobinRouter",
    "SchedulerPolicy",
    "SubmitTimeout",
]
