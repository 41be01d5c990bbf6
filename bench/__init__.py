"""Chip benchmark of the fleet simulator."""
