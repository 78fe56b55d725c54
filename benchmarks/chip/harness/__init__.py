"""The chip benchmark's harness (see ../README.md). Nothing here imports
jax except trace_reduce.py, which run.py starts as a child."""
