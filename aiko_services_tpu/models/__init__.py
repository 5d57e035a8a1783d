from . import llama
from . import moe
from . import nemotron_h
from . import mistral4
from . import sdar
from . import evabyte
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
#: ``slice_key_blocks``).  A module that generates by BLOCK PASSES
#: (``sdar``) also gives ``block_slot_state`` (the leaves a slot's
#: resident state holds for the block in progress: ``window``,
#: ``masked``, ``delivered``, ``passes``, and the slot's schedule),
#: ``MARK_LIVE`` / ``MARK_STORE``, and a config with ``block_length``;
#: its ``serve_chunk_paged`` / ``serve_chunk_mixed`` return, where the
#: others return ``(slots, steps)`` tokens and ``(slots,)`` counts,
#: the ``(slots, passes, block)`` windows and ``(slots, passes)`` marks
#: the engine's ``_commit_block_passes`` reads.  A module that keeps TWO
#: KINDS OF ROW in one pool (``evabyte``: a window's exact rows and one
#: summary row for every chunk behind it) also gives ``check_layout``,
#: ``table_blocks`` and ``slot_blocks`` (how wide a slot's table row is
#: and how many blocks a request holds, laid out ``[ring ‖
#: summaries]``), ``block_kinds``, ``composed_tables`` /
#: ``composed_positions`` (what its programs hand the K/V kernels,
#: reckoned on the device from the absolute position),
#: ``cache_rows`` and ``cache_events`` (what a position holds and
#: reads, and what happened between two, on the host) and
#: ``CACHE_COUNTERS`` (the engine's counters those two feed).
SERVING_MODULES = (llama, nemotron_h, mistral4, sdar, evabyte)


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
