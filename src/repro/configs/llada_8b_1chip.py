"""llada-8b-1chip — LLaDA-8B at its published widths, cut in depth to fit
one TPU v5e chip (16 GB).

Source: GSAI-ML/LLaDA-8B-Instruct ``config.json`` (hidden size 4096, 32
heads of 128, 32 KV heads, MLP 12,288, vocab 126,464, 32 layers).

Cut: ``n_layers`` 32 -> 16; every width is as published. The deployment it
stands for is the full model on two chips as two pipeline stages of 16
layers each; this chip is one stage, with the embedding and the output head
of the whole model (random weights from a seed). That is about 4.5 B
parameters, or 9.1 GB in bf16, which leaves room for the slot pool and the
126 K-wide logit stage. With half the layers, host work and device idle
time are a larger share of a step than in the deployment.
"""
import dataclasses

from repro.configs.llada_8b import CONFIG as _FULL

CONFIG = dataclasses.replace(_FULL, name="llada-8b-1chip", n_layers=16)
