"""One module per reader; a metric's file under metrics/ names its reader.

``read(run, **args)`` gets a ``harness.RunData`` and the metric file's
``args`` and returns a number, or None when it finds nothing to read."""
