"""The ``dense`` family: decoder layers of grouped-query attention and a
SwiGLU MLP (``TransformerLM`` with ``num_experts`` 0).

A family module is what the harness looks up by the program's
``ModelConfig.family`` (``harness.family``): the weights it makes from a
seed in the program's layout, the reference's decoder layer, and the
operations and bytes of its work.  The dense family's are the functions of
``weights.py``, ``reference.py`` and ``costs.py``, which also hold the
pieces every family shares: the key schedule, WRPN and KV
quantize-dequantize, RMSNorm, rotary embedding, attention, the reference's
block-wise forward loop, and the ``qmm`` arithmetic.
"""
from ..costs import (  # noqa: F401
    decode_attn_bytes,
    decode_attn_flops,
    decode_qmm_calls,
    decode_step_flops,
    kv_bytes_per_token,
    prefill_chunk_flops,
    prefill_qmm_calls,
    qmm_least_seconds,
)
from ..reference import layer as reference_layer  # noqa: F401
from ..weights import program_params  # noqa: F401
