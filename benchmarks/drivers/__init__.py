"""One module per kind of window, found by the traffic file's ``driver``."""
