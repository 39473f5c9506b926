"""The plain float32 references. Nothing here imports the program
(``videop2p_tpu``); weights arrive as ``{"path/to/leaf": array}`` made by
``benchmark/harness/weights.py``."""
