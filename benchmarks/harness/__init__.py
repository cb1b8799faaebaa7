"""The benchmark's own machinery: nothing here belongs to one cell."""
