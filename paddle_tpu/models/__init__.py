"""Model zoo: language models (GPT/BERT/ERNIE-style) + hybrid-parallel GPT.

The reference ships vision models only (python/paddle/vision/models); its
language workloads (BERT/ERNIE/GPT-3, SURVEY.md section 6) live in external
repos.
Here they are first-class: these are the flagship models the benchmarks and
the multi-chip dryrun drive.
"""
from .gpt import GPTConfig, GPTModel, GPTForCausalLM  # noqa: F401
from .bert import BertConfig, BertModel, BertForPretraining, ErnieModel  # noqa: F401
from .interop import load_hf_bert, load_hf_gpt2  # noqa: F401
from . import gpt_hybrid  # noqa: F401
