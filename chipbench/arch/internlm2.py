"""InternLM2 (``model_type`` ``internlm2``): the dense GQA decoder, without
qk-norm and with an untied head. Its packed ``wqkv`` is kept as three
matrices of the same sizes, which changes no product."""

from chipbench.arch._dense import *  # noqa: F401,F403
