"""Readers: one module per source of per-layer numbers.  A metric file
names a reader and gives it arguments; read(ctx, **args) returns the
number, or None where it finds nothing to read (the harness then leaves
the metric out of the line)."""
