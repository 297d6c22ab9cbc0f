"""The chip benchmark's yardstick: cell resolution, the peak table, FLOP
counts, trace reduction, the training driver and the run's result line.

Nothing here imports JAX at module level: ``env.prepare`` has to set the
compile cache's environment before JAX is first imported.
"""
