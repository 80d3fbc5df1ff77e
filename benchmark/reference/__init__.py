"""Plain references: each architecture's forward, loss and gradient in
straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.  No Pallas, no amp, no module of
the program: they read the program's parameter pytree by name and nothing
else.  Departures from the published description are noted where they are
made."""
