"""Plain references: NumPy, float32, independent of the package."""
