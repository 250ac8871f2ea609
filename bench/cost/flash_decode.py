"""``flash_decode``: one query token per row attends its live context in
the KV cache. ``live`` is the rows' live tokens summed (each row's position
+ 1); the kernel's length bound skips the rest, so only live K and V count.
``rows`` is how many query rows the call carries."""


def cost(live: int, rows: int, heads: int, kv_heads: int, head_dim: int,
         kv_bytes: int = 4, act_bytes: int = 4) -> tuple:
    flop = 4 * heads * head_dim * live          # q.k and p.v
    kv = 2 * live * kv_heads * head_dim * kv_bytes
    qo = 2 * rows * heads * head_dim * act_bytes
    return flop, kv + qo
