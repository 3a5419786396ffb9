"""Elementwise IP family: the residual add a residual network's
unfused shortcut runs."""
