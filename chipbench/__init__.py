"""The chip benchmark of the VAMPIRE estimation stack (see run.py)."""
