"""The chip benchmark of the GLCM service: one cell per run, driven from
the client's side of ``GLCMEngine``; see ``chipbench/run.py``."""
