"""The chip benchmark of the MG3MConv conv engine (see bench/README.md)."""
