"""The chip benchmark: harness, yardstick and data (see README.md)."""
