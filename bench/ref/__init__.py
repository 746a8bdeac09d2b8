"""Plain float32 references of the benchmark's model families, one module
per ``model_type`` (``<model_type>.py``, entry ``forward(params, tokens,
config, quant=None)``), in straightforward ``jax.numpy``: no kernels, no
cache, no batching of requests.  They import nothing of the program and
read only the weights the benchmark made."""
