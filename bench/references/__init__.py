"""Plain float32 references, independent of src/repro, one module per
model family; a family's glue to the program sits apart at its end."""
