"""The two record searches of the services (`RIDService.search_isas`,
`SCDService.search_operations`) return the finished JSON body, not a
dict.  Every test that reads such an answer parses it here."""

import json


def body_json(body) -> dict:
    assert isinstance(body, bytes), type(body)
    return json.loads(body.decode("utf-8"))
