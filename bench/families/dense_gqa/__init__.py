"""Dense GQA decoder: RMS norm, rotary, grouped-query attention, SwiGLU MLP,
one scan group of identical layers, K and V per KV head in the cache."""
