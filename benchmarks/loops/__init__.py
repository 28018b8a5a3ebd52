"""One module per traffic `kind`; `run.py` finds it by that name."""
