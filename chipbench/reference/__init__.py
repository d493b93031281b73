"""Plain references: what each model family computes, in straightforward
``jax.numpy`` and float32.  Nothing here imports the program."""
