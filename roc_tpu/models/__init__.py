from roc_tpu.models.model import GraphCtx, Model
from roc_tpu.models.gcn import build_gcn
from roc_tpu.models.sage import build_sage
from roc_tpu.models.gin import build_gin
from roc_tpu.models.gat import build_gat
from roc_tpu.models.gatv2 import build_gatv2
from roc_tpu.models.tconv import build_tconv
from roc_tpu.models.gcnii import build_gcnii


def build_model(name: str, layers, dropout_rate: float = 0.5,
                aggr: str = "", heads: int = 8) -> Model:
    """Model registry keyed by the CLI's -model flag.

    aggr="" means "the model's own default" (gcn: sum — the reference's only
    wired AggrType; sage: avg; gin: sum, where a non-sum choice is rejected
    because the GIN update is defined on sums; gcnii: sum, the operator's
    own).  heads only applies to gat, gatv2 and tconv."""
    if name == "gcn":
        return build_gcn(layers, dropout_rate, aggr or "sum")
    if name == "sage":
        return build_sage(layers, dropout_rate, aggr or "avg")
    if name == "gin":
        if aggr not in ("", "sum"):
            raise ValueError("gin is defined on sum aggregation")
        return build_gin(layers, dropout_rate)
    if name == "gat":
        return build_gat(layers, dropout_rate, heads=heads)
    if name == "gatv2":
        return build_gatv2(layers, dropout_rate, heads=heads)
    if name == "tconv":
        return build_tconv(layers, dropout_rate, heads=heads)
    if name == "gcnii":
        if aggr not in ("", "sum"):
            raise ValueError("gcnii is defined on sum aggregation")
        return build_gcnii(layers, dropout_rate)
    raise ValueError(
        f"unknown model {name!r} (gcn|sage|gin|gat|gatv2|tconv|gcnii)")


__all__ = ["Model", "GraphCtx", "build_gcn", "build_sage", "build_gin",
           "build_gat", "build_gatv2", "build_tconv", "build_gcnii",
           "build_model"]
