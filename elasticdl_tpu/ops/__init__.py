"""TPU kernels and collective ops (pallas + shard_map).

The reference has no custom-kernel layer (its compute plane is TF eager);
this package is the TPU build's hot-op layer: a pallas flash-attention
kernel for the MXU and ring attention over the ``sp`` mesh axis for
long-context sequence parallelism.
"""

# NOTE: the dispatch entry point lives at ops.attention.attention; it is
# deliberately NOT re-exported here — a package attribute named like the
# submodule would shadow it for `import elasticdl_tpu.ops.attention`.
from elasticdl_tpu.ops.attention import (  # noqa: F401
    flash_attention,
    mha_reference,
)
from elasticdl_tpu.ops.on_mesh import set_attention_mesh  # noqa: F401
from elasticdl_tpu.ops.pipeline import (  # noqa: F401
    pipeline_apply,
    pipeline_sharding_rules,
)
from elasticdl_tpu.ops.ring_attention import ring_attention  # noqa: F401
from elasticdl_tpu.ops.ulysses import ulysses_attention  # noqa: F401
