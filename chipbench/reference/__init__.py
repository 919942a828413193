"""Plain references: straightforward jax.numpy, float32, no kernels, no
batching tricks.  Nothing here imports the program."""
