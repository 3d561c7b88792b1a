"""executor: device time per optimizer step in class ``token_mix`` of the class
table (``optable.table``): the scopes ``gdn/conv``, ``gdn/gate``, ``norm`` and ``swiglu`` of ``ops.py``: the token model's pointwise work (convolution, gates, norms, the SwiGLU's product). On the chip where it is largest; nothing
where there is no class table."""

import optable


def read(run):
    return optable.class_value(run, "token_mix")
