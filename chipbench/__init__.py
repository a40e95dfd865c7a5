"""The repository's benchmark: one cell, one run, one last line.  See README.md."""
