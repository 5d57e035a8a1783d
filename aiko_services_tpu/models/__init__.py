from . import llama
from . import moe
from . import nemotron_h
from . import mistral4
from . import classifier
from . import detector
from . import asr
from . import vision
from . import speculative
from . import lora

#: The modules a serving engine can bind: each registers its configs
#: in ``CONFIGS`` and gives the engine's entry points
#: (``init_paged_cache``, ``prefill_append_paged``,
#: ``serve_chunk_paged``, ``serve_chunk_mixed``,
#: ``scatter_state_rows``, ``kv_geometry``, ``kv_pool_layers``,
#: ``state_bytes_per_slot``, ``layer_kinds``; ``RECURRENT_STATE``,
#: ``COUNTERS``, ``UNSUPPORTED``; and, where the K/V kernels' own
#: dispatch does not describe its pool, ``attention_paths`` and
#: ``slice_key_blocks``).
SERVING_MODULES = (llama, nemotron_h, mistral4)


def serving_model(config_name: str):
    """``(module, config)`` of a registered config: the module whose
    ``CONFIGS`` holds the name is the one that serves it."""
    for module in SERVING_MODULES:
        if config_name in module.CONFIGS:
            return module, module.CONFIGS[config_name]
    raise KeyError(
        f"no serving config {config_name!r}; registered: "
        + ", ".join(sorted(name for module in SERVING_MODULES
                           for name in module.CONFIGS)))
