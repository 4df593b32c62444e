"""Plain float32 reference of the dense Qwen3 decoder (see ``qwen3``)."""
