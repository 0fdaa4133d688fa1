"""The bus protocol's constants, as the upstream specification gives
them (grapevine.proto request and status enums, README record layout).

The benchmark keeps its own copy: the oracle and the comparison that
decides ``correct`` must not follow the program if the program's
constants drift."""

MSG_ID_SIZE = 16
PUBKEY_SIZE = 32
RECORD_SIZE = 1024  # id 16 | sender 32 | recipient 32 | timestamp 8 | payload
PAYLOAD_SIZE = RECORD_SIZE - (MSG_ID_SIZE + 2 * PUBKEY_SIZE + 8)  # 936

ZERO_MSG_ID = b"\x00" * MSG_ID_SIZE
ZERO_PUBKEY = b"\x00" * PUBKEY_SIZE

SIGNING_CONTEXT = b"grapevine-challenge"
CHALLENGE_SIZE = 32
SIGNATURE_SIZE = 64

CREATE, READ, UPDATE, DELETE = 1, 2, 3, 4

SUCCESS = 1
NOT_FOUND = 2
MESSAGE_ID_ALREADY_IN_USE = 3
INVALID_RECIPIENT = 4
TOO_MANY_MESSAGES_FOR_RECIPIENT = 5
TOO_MANY_RECIPIENTS = 6
TOO_MANY_MESSAGES = 7
