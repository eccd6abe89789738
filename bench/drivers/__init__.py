"""The loop for each kind of cell, found by the configuration's ``driver``."""
