"""Launchers: serving and training entry points, the serving mesh and its
sharding rules."""
