"""One small module per reader, found by name (``layer_metrics/<metric>.json``
names its ``reader``). ``read(params, facts)`` returns a number, or None when
what it reads is not in this run (no trace, another kind of cell): the
harness then leaves the metric out of the line."""
