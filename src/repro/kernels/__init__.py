"""Pallas TPU kernels (+ pure-jnp oracles).

imc_mvm          — INT8 weight-stationary matmul (IMC crossbar analogue)
conv2d           — INT8 direct conv, weight-stationary taps
flash_attention  — online-softmax attention (causal/window/softcap)
ref              — oracles the kernels are checked against

Every kernel takes ``interpret`` explicitly (default ``False``: compile
for the TPU).  Nothing here picks it, or the oracle, from the backend.
"""

from . import ref
from .conv2d import imc_conv2d
from .flash_attention import flash_attention
from .imc_mvm import imc_mvm

__all__ = ["ref", "imc_conv2d", "flash_attention", "imc_mvm"]
