"""Serve kinds: what the serve driver knows of each model architecture."""
