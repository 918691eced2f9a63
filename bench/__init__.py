"""The chip benchmark of the optimizer and its service (see README.md)."""
