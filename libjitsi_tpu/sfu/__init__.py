from libjitsi_tpu.sfu.cache import PacketCache, SlabCache  # noqa: F401
from libjitsi_tpu.sfu.rtcp_termination import RtcpTermination  # noqa: F401
from libjitsi_tpu.sfu.rtx import (RtxReceiver, RtxSender,  # noqa: F401
                                  decapsulate_batch, encapsulate_batch)
from libjitsi_tpu.sfu.simulcast import SimulcastForwarder  # noqa: F401
from libjitsi_tpu.sfu.svc import Vp9SvcForwarder  # noqa: F401
from libjitsi_tpu.sfu.translator import RtpTranslator  # noqa: F401
