"""Design-space configurations of the vector engine (``vector_engine``)."""
