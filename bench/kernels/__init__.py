"""Operations and bytes of each kernel call, from its shapes.

One module per kernel, named as the program names the kernel.  Each
``cost`` returns ``(flops, bytes)``: the multiply-adds the algorithm
needs (2 operations each, padding excluded) and the bytes it has to move
through HBM at least (every operand read once, the output written once,
activations inside a fused chain kept on chip).
"""
