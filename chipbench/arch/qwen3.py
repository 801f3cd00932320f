"""Qwen3 (``model_type`` ``qwen3``): the dense GQA decoder, with per-head
RMSNorm of queries and keys and a tied head."""

from chipbench.arch._dense import *  # noqa: F401,F403
