"""A sparse-expert language model trained through the parameter server
(docs/LM_TRAINER.md)."""

from .model import LMConfig  # noqa: F401
from .ps_train import PSLMTrainer, zipf_tokens  # noqa: F401
