# TPU Pallas kernels for the paper's compute hot-spots:
#   pfels_transmit   — FUSED clip -> rand_k -> power scale -> noisy AirComp
#                      sum for the whole (r, d) batch (Alg. 2 lines 12-15),
#                      one pass over d-tiles, no (r, d) intermediates
#   randk_gather     — A^t Delta + beta-scale (client transmit path)
#   aircomp_combine  — (A^t)^T y / (r beta) scatter + unscale (server path)
#   clip_norm        — fused two-pass l2 clip (Assumption 1)
#   ssd_scan         — Mamba2 SSD chunk scan (ssm/hybrid archs)
#   flash_attn       — flash-attention forward (prefill hot-spot)
#   vit_attention    — ViT self-attention forward and backward, the (t, t)
#                      scores kept in VMEM (models/vit.py local training)
# Each: kernel.py (pl.pallas_call + BlockSpec), ops.py (jit wrapper,
# interpret=True on CPU), ref.py (pure-jnp oracle).
