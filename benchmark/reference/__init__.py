"""Plain references: one forward pass per architecture in straightforward
``jax.numpy``, float32 at ``highest`` matmul precision, with no kernel,
cache, batching or scan. They read the system's own weights and share no
code with ``deepspeed_tpu``."""
