"""The on-chip serving benchmark's yardstick: traffic generation, seeded
weights, the served-path client, the trace reduction, operation and byte
counts, and the comparison that decides ``correct``.

Nothing here imports the program except the system under test itself
(``repro.launch.serve`` to build the engine, ``repro.serving.driver`` to
reach it, and the program's parameter layout by ``jax.eval_shape``)."""
