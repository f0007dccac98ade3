"""olmoe-1b-7b-ep8: one chip's share of OLMoE-1B-7B served 8-way expert
parallel.

Eight chips share each expert layer, eight experts each; this chip holds
experts 0-7 of the 64.  The router keeps its 64 outputs and its 8 experts
per token; the chip computes its own experts' part of each layer
(dropless, :func:`repro.models.moe.moe_held_forward`), and what the other
seven chips' experts would add is left out.  Attention, the embedding and
the LM head are replicated on every chip (data-parallel attention), so
they are whole here, as are all 16 layers and the vocabulary.
"""

import dataclasses

from repro.configs.olmoe_1b_7b import CONFIG as OLMOE

CONFIG = dataclasses.replace(
    OLMOE,
    name="olmoe-1b-7b-ep8",
    moe_held=tuple(range(8)),
    supported_shapes=(),
    microbatch=None,
)
