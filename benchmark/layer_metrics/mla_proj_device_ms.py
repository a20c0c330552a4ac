"""Latent attention's projections (W_q, W_kva, the latent's RMS norm, W_kvb, rotary and W_o; models/deepseek_v3.py), forward, backward and recomputation: self time per step of ops under the
ddlpc/mla/proj scope.  The kernels between them are attention_device_ms."""

import scope_time


def read(run):
    return scope_time.ms_per_step(run, "ddlpc/mla/proj")
