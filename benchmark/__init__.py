"""The benchmark: harness, data files and yardstick of paddle_tpu's cells.

Everything a later PR may not change lives here: traffic generation, the
reduction from traces and stamps to metrics, the table of peaks, the
operation and byte counts, the plain references and the comparison that
decides ``correct``. See README.md.
"""
