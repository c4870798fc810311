"""A2Q reproduction package root."""
