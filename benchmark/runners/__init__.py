"""One runner per ``kind`` of traffic file, found by that name. A runner
exposes ``run(ctx) -> dict`` (see run.py)."""
