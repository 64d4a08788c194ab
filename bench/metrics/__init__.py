"""One file per per-layer metric, named after it: ``read(ctx)`` returns
the metric's value, or None where the traced window holds nothing to
read.  ``kernel_counts`` holds the operations and bytes each kernel's
algorithm needs per call."""
