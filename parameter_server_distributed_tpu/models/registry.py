"""Model registry: name -> (model factory, data factory).

Gives every CLI/benchmark entry point a single switch for the BASELINE
configs: MNIST MLP (config 1), CIFAR ResNet-18 (config 2), 1B MLP
(configs 3/5), ResNet-50 (config 4), plus the transformer LM flagship.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Iterator

from ..data.synthetic import (synthetic_image_batches, synthetic_mnist,
                              synthetic_tokens)
from .mlp import MLP, billion_param_mlp, mnist_mlp
from .resnet import resnet18, resnet50
from .transformer import (llama_350m, lm_350m, moe_350m, moe_lm, small_lm,
                          switch_lm, tiny_lm)
from .vit import vit_s16, vit_tiny


# xy loaders: the registry seed varies the SAMPLING stream only — the
# generated dataset (the task) is fixed, like real MNIST.  Seeding the
# dataset itself would hand differently-seeded consumers (PS workers,
# --per-process-data hosts, the eval stream) unrelated tasks.
def _mnist_batches(batch_size: int, seed: int) -> Iterator:
    return synthetic_mnist(seed=0).batch_stream(batch_size, seed=seed)


def _cifar_batches(batch_size: int, seed: int) -> Iterator:
    return synthetic_image_batches(batch_size, image_size=32, seed=seed)


def _imagenet_batches(batch_size: int, seed: int) -> Iterator:
    return synthetic_image_batches(batch_size, image_size=224,
                                   num_classes=1000, seed=seed)


def _lm_batches(batch_size: int, seed: int) -> Iterator:
    return synthetic_tokens(batch_size, seq_len=256, vocab=1024, seed=seed)


def _lm_350m_batches(batch_size: int, seed: int) -> Iterator:
    return synthetic_tokens(batch_size, seq_len=1024, vocab=32000, seed=seed)


def _mlp_1b_batches(batch_size: int, seed: int) -> Iterator:
    import numpy as np
    rng = np.random.default_rng(seed)
    hidden = 16384
    while True:
        x = rng.standard_normal((batch_size, hidden)).astype(np.float32)
        y = rng.integers(0, hidden, batch_size).astype(np.int32)
        yield x, y


# name -> (model factory, synthetic data factory, file-data kind)
# file-data kind: "tokens" (memmap .bin shard, data/files.token_stream) or
# "xy" (npz with x/y arrays, data/files.npz_stream)
# Factories may accept dtype=/remat= keywords; get_model_and_batches passes
# only what each signature supports.
REGISTRY: dict[str, tuple[Callable, Callable[[int, int], Iterator], str]] = {
    "mnist_mlp": (mnist_mlp, _mnist_batches, "xy"),
    "resnet18_cifar": (partial(resnet18, num_classes=10),
                       _cifar_batches, "xy"),
    "resnet50_imagenet": (partial(resnet50, num_classes=1000),
                          _imagenet_batches, "xy"),
    "small_lm": (partial(small_lm, vocab=1024, seq=256),
                 _lm_batches, "tokens"),
    "tiny_lm": (partial(tiny_lm, vocab=1024, seq=256),
                _lm_batches, "tokens"),
    "small_lm4": (partial(small_lm, vocab=1024, seq=256, n_layers=4),
                  _lm_batches, "tokens"),
    "moe_lm": (partial(moe_lm, vocab=1024, seq=256),
               _lm_batches, "tokens"),
    "moe_lm_top2": (partial(moe_lm, vocab=1024, seq=256, top_k=2),
                    _lm_batches, "tokens"),
    "switch_lm": (partial(switch_lm, vocab=1024, seq=256),
                  _lm_batches, "tokens"),
    "mlp_1b": (billion_param_mlp, _mlp_1b_batches, "xy"),
    "lm_350m": (lm_350m, _lm_350m_batches, "tokens"),
    "lm_350m_gqa": (partial(lm_350m, kv_heads=4), _lm_350m_batches,
                    "tokens"),
    # head_dim-128 flagship: 8 heads x 128 — a full MXU tile per
    # attention matmul, one head a row of lanes in the attention kernel
    "lm_350m_hd128": (partial(lm_350m, n_heads=8), _lm_350m_batches,
                      "tokens"),
    # LLaMA-architecture flagship (SwiGLU + GQA): the shape from_hf_llama
    # conversions have, so what is measured on it transfers to real checkpoints
    "llama_350m": (llama_350m, _lm_350m_batches, "tokens"),
    # flagship-scale sparse MoE: lm_350m's trunk, every 2nd FFN routed
    # over 8 experts (~350M active / ~1.07B total)
    "moe_350m": (moe_350m, _lm_350m_batches, "tokens"),
    # vision transformers (models/vit.py): CIFAR-scale and ImageNet-scale
    "vit_tiny_cifar": (partial(vit_tiny, num_classes=10, image_size=32),
                       _cifar_batches, "xy"),
    "vit_s16_imagenet": (partial(vit_s16, num_classes=1000,
                                 image_size=224),
                         _imagenet_batches, "xy"),
}

DTYPE_NAMES = {"f32": "float32", "float32": "float32",
               "bf16": "bfloat16", "bfloat16": "bfloat16"}


def resolve_dtype(name: str):
    """Flag string -> jnp dtype; single owner of the alias table and its
    error (cli/generate_main's --hf-gpt2 path reuses it)."""
    if name not in DTYPE_NAMES:
        raise ValueError(f"unknown dtype {name!r}; "
                         f"options {sorted(set(DTYPE_NAMES))}")
    import jax.numpy as jnp

    return getattr(jnp, DTYPE_NAMES[name])


def _model_kwargs(model_fn: Callable, name: str, dtype: str,
                  remat: bool | None, scan: bool | None = None,
                  seq_len: int = 0, remat_policy: str = "") -> dict:
    """The subset of {dtype, remat} this factory supports; error (rather
    than silently ignore) when the user asked for one it doesn't."""
    import inspect

    sig = inspect.signature(model_fn)
    has_var_kw = any(p.kind is p.VAR_KEYWORD for p in sig.parameters.values())
    kwargs: dict = {}
    if dtype:
        resolved = resolve_dtype(dtype)
        if not (has_var_kw or "dtype" in sig.parameters):
            raise ValueError(f"model {name!r} does not take a dtype")
        kwargs["dtype"] = resolved
    if remat is not None:
        if has_var_kw or "remat" in sig.parameters:
            kwargs["remat"] = remat
        elif remat:
            # asking for remat on a model that can't honor the memory
            # saving is an error; forcing it OFF on a model that never
            # remats is a no-op (lets --no-remat sweep across the whole
            # registry)
            raise ValueError(f"model {name!r} does not support remat "
                             f"(transformer LMs only)")
    if scan is not None:
        if has_var_kw or "scan_layers" in sig.parameters:
            kwargs["scan_layers"] = scan
        elif scan:
            raise ValueError(f"model {name!r} does not support scan_layers "
                             f"(dense transformer LMs only)")
    if seq_len:
        if not (has_var_kw or "seq" in sig.parameters):
            raise ValueError(f"model {name!r} has no sequence length "
                             f"(transformer LMs only)")
        kwargs["seq"] = seq_len
    if remat_policy:
        if not (has_var_kw or "remat_policy" in sig.parameters):
            raise ValueError(f"model {name!r} does not support remat_policy "
                             f"(flagship transformer LMs only)")
        kwargs["remat_policy"] = remat_policy
    return kwargs


def get_model_and_batches(name: str, batch_size: int, seed: int = 0,
                          data_path: str = "", dtype: str = "",
                          remat: bool | None = None,
                          scan: bool | None = None,
                          seq_len: int = 0, remat_policy: str = ""):
    """Build (model, batch iterator).  ``data_path`` switches from the
    synthetic loaders to file-backed data (data/files.py), dispatched by
    the registry entry's declared file-data kind.  ``dtype`` ("f32"/"bf16"),
    ``remat``, and ``scan`` (lax.scan over stacked layers) forward to
    factories that support them; remat/scan are tri-state — None keeps the
    factory's default (e.g. lm_350m defaults remat on), True/False force
    it for factories that take the keyword.  ``seq_len`` overrides the
    sequence length for transformer LMs (long-context runs, e.g.
    lm_350m at 4096); the synthetic token stream follows the model."""
    if name not in REGISTRY:
        raise ValueError(f"unknown model {name!r}; have {sorted(REGISTRY)}")
    model_fn, data_fn, file_kind = REGISTRY[name]
    model = model_fn(**_model_kwargs(model_fn, name, dtype, remat, scan,
                                     seq_len, remat_policy))
    if not data_path:
        if seq_len and file_kind == "tokens":
            # the factory's synthetic stream bakes in the default seq; at
            # an overridden length, stream crops matching the model
            from ..data.synthetic import synthetic_tokens
            return model, synthetic_tokens(
                batch_size, seq_len=model.config.max_seq,
                vocab=model.config.vocab, seed=seed)
        return model, data_fn(batch_size, seed)
    if file_kind == "tokens":
        batches = lm_batches(model, batch_size, seed=seed,
                             data_path=data_path)
    else:
        from ..data.files import npz_stream
        batches = npz_stream(data_path, batch_size, seed=seed)
    return model, batches


def lm_batches(model, batch_size: int, seed: int = 0, data_path: str = ""):
    """Token batches for an arbitrary transformer LM — the registry's
    "tokens" data branch exposed for models built OUTSIDE the registry
    (HF conversions, hand-constructed configs): file-backed data when
    ``data_path`` is set (.txt byte-tokenized via data/text.py, else a
    token memmap via data/files.py), synthetic (vocab, max_seq) crops
    otherwise."""
    if not data_path:
        from ..data.synthetic import synthetic_tokens
        return synthetic_tokens(batch_size, seq_len=model.config.max_seq,
                                vocab=model.config.vocab, seed=seed)
    if data_path.endswith(".txt"):
        # raw text corpus: byte-tokenize to a cached shard on first use;
        # the model's vocab must cover the byte tokenizer's 258 ids
        from ..data.text import ByteTokenizer, require_vocab, text_stream
        tok = ByteTokenizer()
        require_vocab(model.config.vocab, tok)
        return text_stream(data_path, batch_size,
                           seq_len=model.config.max_seq, seed=seed,
                           tokenizer=tok)
    from ..data.files import token_stream
    return token_stream(data_path, batch_size,
                        seq_len=model.config.max_seq, seed=seed,
                        vocab=model.config.vocab)
