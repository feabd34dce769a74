"""ReLeQ reproduction package."""
