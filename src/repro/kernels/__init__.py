# Pallas TPU kernels for the framework's compute hot spots:
#   wkv             — Stage-1 RWKV delta-rule recurrence (chunked, state in VMEM)
#   flash_attention — streaming-softmax attention for the zoo archs
#   set_attention   — fused masked, frequency-weighted set attention for the
#                     Stage-2 Set Transformer SAB/PMA (scores stay in VMEM)
#   kmeans_assign   — tiled distance+argmin for universal clustering
# Each package has: <name>.py (pl.pallas_call + BlockSpec), ops.py (jit'd
# wrapper), ref.py (pure-jnp oracle used by the allclose test sweeps).
#
# impl= convention (shared by all four families): model/loss entry points
# take impl="xla" | "pallas" | "pallas_interpret".
#   "xla"              — pure-jnp path (ref math), runs anywhere, autodiff ok
#   "pallas"           — compiled TPU kernel (forward only unless the family
#                        defines a custom VJP; set_attention does — its fused
#                        backward makes Stage-2 training impl="pallas" clean)
#   "pallas_interpret" — same kernel via the Pallas interpreter; slow but
#                        runs on CPU, used by parity tests and benchmarks
# The flag is threaded as a static argument (baked into jax.jit partials),
# so switching impl never retraces existing entry points.
