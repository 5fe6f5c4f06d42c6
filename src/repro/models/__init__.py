"""repro.models — LM stack for the ten assigned architectures."""

from .config import ModelConfig
from .transformer import LM, StackSpec, kv_read

__all__ = ["ModelConfig", "LM", "StackSpec", "kv_read"]
