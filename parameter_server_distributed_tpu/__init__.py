"""TPU-native parameter-server distributed training framework.

A ground-up JAX/XLA re-design of the capabilities of the C++/gRPC
parameter-server reference (araju6/parameter-server-distributed):

- control plane: coordinator (registration / heartbeats / stale eviction /
  PS discovery) and parameter-server RPC surface (push / pull / sync-status /
  checkpoint save-load), wire-compatible with the reference's proto3 services
  (reference: proto/parameter_server.proto, proto/coordinator.proto).
- data plane: jitted SPMD train steps over a `jax.sharding.Mesh`; gradient
  mean via `psum`/`pmean` over ICI replaces the NCCL all-reduce
  (reference: src/nccl_manager.cpp); ZeRO-style sharded parameter/optimizer
  state with reduce-scatter + all-gather replaces the PS push/pull data path
  (reference: src/parameter_server.cpp).
- extensions beyond the reference: async / bounded-staleness SGD, elastic
  barrier width, real model zoo (MLP / ResNet / Transformer), ring attention
  for sequence parallelism, pallas kernels, benchmarks and tests.

Import as ``import parameter_server_distributed_tpu as pst``.
"""

__version__ = "0.2.0"

# Keep the top-level import light: no jax import here so that control-plane
# tooling (coordinator CLI, wire codec) can run without touching a device.
# The platform is JAX's own business (JAX_PLATFORMS); the compile cache is
# placed by utils/compile_cache.py, which the compiling entry points call.


# Lazy top-level API: the common entry points resolve on first access so
# the bare import stays device- and jax-free (control-plane tools depend
# on that).
_API = {
    "run_training": ("parallel.train_loop", "run_training"),
    "TrainLoopConfig": ("parallel.train_loop", "TrainLoopConfig"),
    "generate": ("models.generation", "generate"),
    "beam_search": ("models.generation", "beam_search"),
    "speculative_generate": ("models.generation", "speculative_generate"),
    "quantize_params": ("models.quant", "quantize_params"),
    "DecodeServer": ("models.serving", "DecodeServer"),
    "from_hf_gpt2": ("models.hf", "from_hf_gpt2"),
    "from_hf_llama": ("models.hf", "from_hf_llama"),
    "to_hf_gpt2": ("models.hf", "to_hf_gpt2"),
    "to_hf_llama": ("models.hf", "to_hf_llama"),
    "get_model_and_batches": ("models.registry", "get_model_and_batches"),
    "Transformer": ("models.transformer", "Transformer"),
    "TransformerConfig": ("models.transformer", "TransformerConfig"),
    "MeshConfig": ("config", "MeshConfig"),
    "build_mesh": ("parallel.mesh", "build_mesh"),
    "ShardedTrainer": ("parallel.train_step", "ShardedTrainer"),
}


def __getattr__(name: str):
    try:
        module, attr = _API[name]
    except KeyError:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}") from None
    import importlib

    return getattr(importlib.import_module(f".{module}", __name__), attr)


def __dir__():
    return sorted(list(globals()) + list(_API))
