"""Plain float32 forward passes that decide whether a served run is correct."""
