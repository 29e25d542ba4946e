"""LoRA serving: adapter sources, batched multi-LoRA execution, routing.

Ref: lib/llm/src/lora.rs (downloader/cache/routing/controller, ~8.7k LoC).
The reference delegates LoRA *execution* to its backend engines (vLLM
punica kernels) and owns discovery/placement; here the JAX engine is the
backend, so execution lives in this repo too: a stacked adapter bank on
device with per-slot adapter indices — every request in a batch can use a
different adapter (or none) in the same compiled program
(`lora/bank.py`), the S-LoRA/punica idea expressed as static-shape
einsums XLA can fuse instead of custom gather kernels.

`lora.bank` (the device half) is imported by module path, never from
here: the KV router imports `lora.routing` inside the frontend, and a
frontend must stay JAX-free (it runs beside the worker that owns the
chip).
"""

from .routing import LoraReplicaSelector, rendezvous_ranking  # noqa: F401
from .source import LocalLoraSource, LoraAdapter  # noqa: F401
