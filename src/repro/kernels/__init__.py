# Pallas TPU kernels for the compute hot-spots of the HAPFL train/serve path:
#   flash_attention — block-wise attention (prefill/train)
#   kd_loss         — fused mutual-KD (CE + bidirectional KL) over vocab tiles
#   rmsnorm         — row-tiled norm
# ops.py = jit'd wrappers (interpret=True on the CPU backend only);
# ref.py = pure-jnp oracles.
from repro.kernels.ops import (flash_attention_op, kd_loss_op, rmsnorm_op,
                               mutual_kd_loss, interpret_mode)
# sharded.py = shard_map'd row/batch-parallel wrappers over a device mesh
from repro.kernels.sharded import (sharded_flash_attention, sharded_kd_loss,
                                   sharded_rmsnorm)
